"""Names on Ferret's layers, on the profiler's clock.

(a) the engine's compiled scan carries every ``ferret.*`` scope in its
    ops' metadata, and the scopes change nothing else in the program;
(b) with the Pallas kernels on (interpret mode) each kernel carries its
    name, under ``ferret.compensate``;
(c) the pipelined and the elastic segment loops leave their ``ferret.*``
    host spans in a profiler trace, in order, segment by segment;
(d) the counters the spans feed (``take_wait_s``, ``take_s``,
    ``replan_s``, ``remap_s``) are the spans' own durations, and the
    segment wall times that refine the profile end at the fetch of the
    segment's results.
"""

import contextlib
import dataclasses
import glob
import math
import re

import jax
import jax.numpy as jnp
import pytest

from repro.api import as_stream_source
from repro.core import compensation as comp
from repro.core import pipeline as pl
from repro.core import schedule as sch
from repro.core.cost_model import PipelineConfig, StageKnobs, WorkerConfig
from repro.core.ferret import FerretConfig, FerretTrainer
from repro.core.profiler import ModelProfile, analytic_profile
from repro.models import transformer as T
from repro.models.registry import get_config
from repro.ocl.streams import StreamConfig, make_stream
from repro.optim.optimizers import adamw
from repro.runtime import BudgetEvent, ElasticStreamTrainer

SCOPES = ("ferret.forward", "ferret.penalty", "ferret.push", "ferret.delta_gather",
          "ferret.compensate", "ferret.optimizer", "ferret.delta_ring")
TOL_S = 2e-3  # a span's trace event brackets its perf_counter pair


def _cfg():
    return dataclasses.replace(
        get_config("h2o-danube-1.8b", smoke=True),
        compute_dtype="float32", num_layers=4, vocab_size=32,
    )


def _engine(cfg, params, rounds=6):
    bounds = [0, 2, 4]
    staged = pl.staged_from_transformer(cfg, bounds)
    pcfg = PipelineConfig(workers=[WorkerConfig(n, 0, [StageKnobs(), StageKnobs()])
                                   for n in range(2)])
    schedule = sch.build_schedule(pcfg, 2, rounds)
    eng = pl.FerretEngine(
        staged, schedule, adamw(lr=1e-3),
        comp.CompensationConfig(method="iter_fisher", eta_lambda=1e-4), lr=1e-3,
        penalty_fn=lambda stages, w: w * sum(jnp.sum(p ** 2) for p in jax.tree.leaves(stages)),
    )
    state = eng.init_state(T.split_stage_params(cfg, params, bounds))
    toks = jax.random.randint(jax.random.PRNGKey(1), (rounds, 2, 17), 0, cfg.vocab_size)
    stream = {"tokens": toks[:, :, :-1], "labels": toks[:, :, 1:]}
    return eng, state, stream


def _op_names(hlo: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', hlo))


@pytest.fixture(scope="module")
def toy():
    cfg = _cfg()
    return cfg, T.init_params(cfg, jax.random.PRNGKey(0))


def test_engine_scan_carries_every_scope(toy):
    cfg, params = toy
    eng, state, stream = _engine(cfg, params)
    names = _op_names(eng.lower(state, stream, jnp.float32(1e-4)).compile().as_text())
    for scope in SCOPES:
        assert any(scope in n for n in names), scope
    # the backward carries the forward's scope under the transpose
    assert any("transpose(jvp(ferret.forward))" in n for n in names)


def test_scopes_change_only_metadata(toy, monkeypatch):
    cfg, params = toy

    def compiled_text():
        eng, state, stream = _engine(cfg, params)
        text = eng.lower(state, stream, jnp.float32(1e-4)).compile().as_text()
        return re.sub(r",? metadata=\{[^}]*\}", "", text)

    texts = []
    for scoped in (True, False):  # one call site: the stack frames agree
        if not scoped:
            monkeypatch.setattr(pl.jax, "named_scope", lambda name: contextlib.nullcontext())
        texts.append(compiled_text())
    # a scope name, not "ferret.": the text's stack-frame table may list
    # core/ferret.py when a trace cached by an earlier test is reused
    assert not any(scope in texts[1] for scope in SCOPES) and texts[0] == texts[1]


def test_kernels_carry_their_names_in_interpret_mode(toy, monkeypatch):
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    cfg, params = toy
    eng, state, stream = _engine(cfg, params)
    names = _op_names(eng.lower(state, stream, jnp.float32(1e-4)).compile().as_text())
    for kernel in ("iter_fisher_compensate", "iter_fisher_stats"):
        assert any(f"ferret.compensate/{kernel}/" in n for n in names), kernel


# ---------------------------------------------------------------------------
# host spans, read back from a profiler trace
# ---------------------------------------------------------------------------


def _host_spans(trace_dir) -> list:
    """The trace's ``ferret.*`` host events as (name, start_s, end_s,
    stats), in order of start."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(str(trace_dir / "plugins" / "profile" / "*" / "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("ferret."):
                    out.append((e.name, 1e-9 * e.start_ns, 1e-9 * (e.start_ns + e.duration_ns),
                                dict(e.stats)))
    return sorted(out, key=lambda s: s[1])


def _segments(spans) -> list:
    """Per ``ferret.segment`` (by step): the loop's own spans inside it,
    in order, a repeated name once (the feeder's are left out)."""
    out = []
    for name, a, b, stats in spans:
        if name != "ferret.segment":
            continue
        inner = [s for s in spans if a <= s[1] and s[2] <= b and s[0] != "ferret.segment"
                 and not s[0].startswith("ferret.feeder.")]
        names = [s[0] for s in inner]
        names = [n for i, n in enumerate(names) if i == 0 or names[i - 1] != n]
        out.append((int(stats["step_num"]), names, inner))
    return out


def _stream(length):
    return as_stream_source(make_stream(StreamConfig(
        kind="drift", modality="tokens", length=length, batch=2, vocab=32, seq=16)))


def _ferret_cfg(**over):
    base = dict(budget_bytes=math.inf, lr=5e-3, max_workers=3, max_stages=4,
                compensation=comp.CompensationConfig(method="iter_fisher", eta_lambda=1e-4))
    base.update(over)
    return FerretConfig(**base)


PIPELINED = ["ferret.take", "ferret.schedule", "ferret.upload", "ferret.dispatch", "ferret.fetch"]


def test_pipelined_segments_leave_their_spans_in_order(toy, tmp_path, monkeypatch):
    cfg, params = toy
    walls = []

    def observe(model_cfg, batch, seq, profile, plan, rounds, seconds):
        walls.append(seconds)
        return None

    import repro.profile.bridge as bridge

    monkeypatch.setattr(bridge, "observe_segment", observe)
    trainer = FerretTrainer(cfg, _ferret_cfg(profile_feedback=True), batch=2, seq=16)
    with jax.profiler.trace(str(tmp_path)):
        res = trainer.run_stream(params, _stream(16), segment_rounds=8)
    spans = _host_spans(tmp_path)
    segs = _segments(spans)
    assert [(k, names) for k, names, _ in segs] == [(0, PIPELINED), (1, PIPELINED)]
    # the feeder's wait is what take_wait_s sums
    waits = [b - a for n, a, b, _ in spans if n == "ferret.feeder.wait"]
    assert waits and res.stream_wait_s == pytest.approx(sum(waits), abs=TOL_S * len(waits))
    # segment 1's wall time (segment 0 compiles, and is not observed) runs
    # from the dispatch to the end of the fetch
    (_, _, inner), = [s for s in segs if s[0] == 1]
    ev = {n: (a, b) for n, a, b, _ in inner}
    assert walls == [pytest.approx(ev["ferret.fetch"][1] - ev["ferret.dispatch"][0], abs=TOL_S)]
    assert walls[0] >= ev["ferret.fetch"][1] - ev["ferret.fetch"][0]


def _hetero_profile(cfg) -> ModelProfile:
    """Per-layer times scaled 1×..4× so a budget change moves the partition."""
    base = analytic_profile(cfg, 2, 16)
    layers = [dataclasses.replace(ly, t_fwd=ly.t_fwd * (1 + i), t_bwd=ly.t_bwd * (1 + i))
              for i, ly in enumerate(base.layers)]
    return ModelProfile(layers=layers, embed_bytes=base.embed_bytes, batch=2, seq=16)


def test_elastic_switch_spans_match_the_segment_report(toy, tmp_path):
    cfg, params = toy
    et = ElasticStreamTrainer(cfg, _ferret_cfg(), batch=2, seq=16, profile=_hetero_profile(cfg))
    full = et.plan_for(math.inf)
    with jax.profiler.trace(str(tmp_path)):
        res = et.run_stream(params, _stream(20), schedule=[BudgetEvent(10, full.memory * 0.3)])
    segs = _segments(_host_spans(tmp_path))
    switch = ["ferret.replan", "ferret.remap", "ferret.refresh"]
    assert [s.replanned for s in res.segments] == [False, True]
    assert [(k, names) for k, names, _ in segs] == [(0, PIPELINED), (1, switch + PIPELINED)]
    for report, (_, _, inner) in zip(res.segments, segs):
        ev = {}
        for n, a, b, _ in inner:
            ev.setdefault(n, []).append((a, b))
        (take,) = ev["ferret.take"]
        assert report.take_s == pytest.approx(take[1] - take[0], abs=TOL_S)
        if report.replanned:
            ((a, b),) = ev["ferret.replan"]
            assert report.replan_s == pytest.approx(b - a, abs=TOL_S)
            ((a, b),) = ev["ferret.remap"]
            assert report.remap_s == pytest.approx(b - a, abs=TOL_S)
        # the run time covers the fetch of the segment's results
        (fetch,) = ev["ferret.fetch"]
        assert report.run_s == pytest.approx(fetch[1] - ev["ferret.schedule"][0][0], abs=TOL_S)
