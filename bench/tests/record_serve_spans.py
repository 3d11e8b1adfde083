"""Record the serving trace that `test_bench_spans.py` reads.

    PYTHONPATH=src python3 bench/tests/record_serve_spans.py <out.xplane.pb>

On one TPU chip: qwen2-1.5b's attention and FFN widths cut to 2 layers
and a 4,096-token vocabulary, served by `Engine.serve` in mixed mode over
a paged pool with the `flashd_pallas` kernels. Two requests arrive at
t = 0 and a third at 0.3 s, once both have finished, so the trace holds
packed mixed steps, decode chunks and a wait for an arrival. Two calls
of the same pattern compile every program before the profiler starts,
which traces a third `serve()` call alone, with the default options of
`jax.profiler.start_trace`, as `bench/run.py` records a window. The copy
it writes leaves out the compiled programs' HLO protos, which no reader
reads.
"""

import dataclasses
import glob
import json
import os
import shutil
import sys
import tempfile

import jax
import numpy as np

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MAX_NEW = 8


def drop_program_protos(src: str, out: str) -> None:
    """Copy the trace without its `/host:metadata` plane, which holds the
    compiled programs' HLO protos (a megabyte here) and no event."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    keep = [p for p in space.planes if p.name != "/host:metadata"]
    del space.planes[:]
    space.planes.extend(keep)
    with open(out, "wb") as f:
        f.write(space.SerializeToString())


def main(out: str) -> None:
    from repro.models import ModelConfig, get_model
    from repro.serve import Engine, ServeConfig
    from repro.serve.scheduler import Request

    with open(os.path.join(BENCH_DIR, "configs", "qwen2-1.5b.json")) as f:
        model = json.load(f)["model"]
    cfg = dataclasses.replace(ModelConfig(name="qwen2-1.5b", **model),
                              n_layers=2, vocab_size=4096)
    params = get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    eng = Engine(params, cfg, ServeConfig(
        max_batch=4, max_len=512, kv_layout="paged", step_mode="mixed",
        prefill_chunk=128, decode_chunk=8, kv_pool_tokens=4096))
    rng = np.random.default_rng(0)

    def requests(arrivals):
        lengths = (100, 40, 70)
        return [Request(rid=i, prompt=rng.integers(1, 4096, n).astype(np.int32),
                        not_before=t)
                for i, (n, t) in enumerate(zip(lengths, arrivals))]

    for _ in range(2):  # compile every program the traced call runs
        eng.serve(requests([0.0, 0.0, 0.3]), MAX_NEW)
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    try:
        eng.serve(requests([0.0, 0.0, 0.3]), MAX_NEW)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    drop_program_protos(path, out)
    shutil.rmtree(tmp)
    print(f"{out}: {os.path.getsize(out)} bytes; stats {eng.stats()['steps']}")


if __name__ == "__main__":
    main(sys.argv[1])
