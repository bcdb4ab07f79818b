#!/usr/bin/env python3
"""Record the small xplane file that benchmark/tests/test_program_spans.py
reads: a toy engine (2 layers at the published head size, 4 decode slots)
serving four requests over both prefill paths on the chip, the profiler
open over all of it, so that the file holds the program's regions
(`engine.iter` and its phases, `prefill.*`) beside the device's operations.
The Python tracer is off: the regions are the host events that matter here,
and the file stays small.

    python3 benchmark/tools/record_tiny_spans.py <out.xplane.pb>"""

import glob
import os
import shutil
import sys
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from ray_tpu.models import ModelConfig, init_params  # noqa: E402
from ray_tpu.serve.engine import EngineConfig, InferenceEngine  # noqa: E402


def main(out: str) -> None:
    cfg = ModelConfig(name="toy", vocab_size=512, d_model=256, n_layers=2,
                      n_heads=2, n_kv_heads=1, head_dim=128, d_ff=512,
                      max_seq_len=512, dtype="bfloat16")
    params = init_params(cfg, jax.random.PRNGKey(0))
    engine = InferenceEngine(params, cfg, EngineConfig(
        max_batch_size=4, max_pages=65, max_seq_len=512,
        prefill_buckets=(64, 128), prefill_chunk=128, decode_span=4))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(3, cfg.vocab_size, n).tolist()
               for n in (40, 300, 100, 200)]
    engine.generate(prompts[0], max_tokens=6)  # compile outside the trace
    engine.generate(prompts[1], max_tokens=6)
    logdir = os.path.join(os.path.dirname(os.path.abspath(out)), "_tiny_spans")
    shutil.rmtree(logdir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1  # TraceAnnotations are level 1; XLA runtime chatter is 2
    options.enable_hlo_proto = False  # the programs' HLO is most of such a file
    jax.profiler.start_trace(logdir, profiler_options=options)
    threads = [threading.Thread(target=engine.generate, args=(p,),
                                kwargs={"max_tokens": 6}) for p in prompts]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    jax.profiler.stop_trace()
    engine.stop()
    found = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    shutil.copyfile(found[0], out)
    shutil.rmtree(logdir, ignore_errors=True)
    print(out, os.path.getsize(out))


if __name__ == "__main__":
    main(sys.argv[1])
