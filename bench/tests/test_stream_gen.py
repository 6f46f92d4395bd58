"""The drift stream: the same seed gives the same rounds however the
stream is cut into takes, another seed other rounds, and its host cost
per round is printed."""

from __future__ import annotations

import numpy as np

from stream_gen import DriftStream

KW = dict(successors=8, period_rounds=512, sharpness=8.0)


def test_same_seed_same_rounds():
    a = DriftStream(2048, 4, 512, 3_000_000_007, **KW)
    b = DriftStream(2048, 4, 512, 3_000_000_007, **KW)
    whole = a.rows(0, 12)
    parts = [b.rows(0, 5), b.rows(5, 7)]
    for k in ("tokens", "labels"):
        np.testing.assert_array_equal(whole[k], np.concatenate([p[k] for p in parts]))
        assert whole[k].shape == (12, 4, 512) and whole[k].dtype == np.int32
    np.testing.assert_array_equal(whole["tokens"][:, :, 1:], whole["labels"][:, :, :-1])
    other = DriftStream(2048, 4, 512, 3_000_000_008, **KW).rows(0, 12)
    assert not np.array_equal(whole["tokens"], other["tokens"])


def test_tokens_follow_the_kernels_and_drift():
    s = DriftStream(256, 4, 64, 1, **KW)
    assert s.mix(0) == 0.0 and abs(s.mix(256) - 1.0) < 1e-12
    rows = s.rows(0, 2)
    prev, nxt = rows["tokens"], rows["labels"]
    # at round 0 every step goes through T0: each label is a successor
    allowed = s.next[0][prev[0]]
    assert np.all((allowed == nxt[0][..., None]).any(-1))
    assert rows["tokens"].max() < 256


def test_cost_per_round_is_printed():
    """Host time per round of 4 x 512 tokens (``pytest -s`` shows it); a
    device round takes tens of ms, so this has to stay far below."""
    for vocab in (2048, 4000):
        s = DriftStream(vocab, 4, 512, 5, **KW)
        s.rows(0, 32)
        ms = 1e3 * s.gen_s / s.rounds_made
        print(f"drift stream, vocab {vocab}: {ms:.3f} ms of host time per round")
        assert ms < 20.0
