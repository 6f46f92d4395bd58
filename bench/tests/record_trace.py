"""Record the small chip trace with the program's names (one TPU v5e).

    python3 bench/tests/record_trace.py <out.xplane.pb.gz>

The engine at a small width (d_model 256, 2 layers, P=2; ER, Iter-Fisher,
AdamW), 8-round segments of 4 x 128 new tokens and 8 replay rows, through
``FerretSession.run("pipelined")``. The trace begins when the feeder takes
the rows of segment 2, so inside the run of segment 1 (a reader skips that
run), and ends with the stream after segment 2. The Python tracer is off,
which keeps the file small; the ``ferret.*`` host spans are kept.
"""

from __future__ import annotations

import glob
import gzip
import json
import shutil
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

MODEL = {"num_layers": 2, "d_model": 256, "num_heads": 4, "num_kv_heads": 4, "d_ff": 1024,
         "vocab_size": 2048, "window": None, "rope_theta": 10000.0, "norm_eps": 1e-5,
         "param_dtype": "float32", "compute_dtype": "bfloat16"}
SEGMENTS = 3


def main(out: str) -> int:
    import jax

    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace: no TPU")
    import drive
    import spec as spec_lib
    from repro.api.streams import StreamSource
    from stream_gen import DriftStream

    traffic = json.loads((BENCH / "traffic" / "stream.json").read_text())
    traffic.update(batch=4, seq=128, segment_rounds=8, name="stream-small")
    config = {"name": "small", "registry_name": "musicgen-medium", "reference": "decoder",
              "model": MODEL}
    decoder = spec_lib.Spec(ROOT, BENCH).reference("decoder")
    cell = spec_lib.Cell("small.stream", 1, config, traffic, {}, [], [], decoder)
    params = drive.make_params(decoder.param_shapes(MODEL), 1)
    gen = DriftStream.from_traffic(traffic, MODEL["vocab_size"], 1)
    tmp = tempfile.mkdtemp()

    class Source(StreamSource):
        """``SEGMENTS`` segments; the trace starts with take 3 (the rows of
        segment 2), which the feeder makes as segment 1 starts."""

        def __init__(self):
            self.takes = 0

        @property
        def length(self):
            return None

        @property
        def remaining(self):
            return None

        def take(self, n):
            self.takes += 1
            if self.takes == 3:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(tmp, profiler_options=opts)
            if self.takes > SEGMENTS:
                return None
            return gen.rows((self.takes - 1) * n, n)

    session = drive.make_session(cell, params)
    session.run("pipelined", stream=Source(), segment_rounds=traffic["segment_rounds"])
    jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb")
    with open(path, "rb") as f, gzip.open(out, "wb", compresslevel=9) as g:
        shutil.copyfileobj(f, g)
    shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps({"out": out, "bytes": Path(out).stat().st_size}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
