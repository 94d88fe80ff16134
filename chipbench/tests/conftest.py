"""CPU tests of the benchmark at smoke size.

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests
"""

import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import program  # noqa: E402,F401  (puts src on the path)
import run  # noqa: E402

# the widths of the program's smoke configurations
SMOKE_MODEL = {"num_hidden_layers": 2, "hidden_size": 128, "num_attention_heads": 4,
               "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 256,
               "vocab_size": 515}
SMOKE_TRAFFIC = {"train": {"batch": 4, "seq_len": 32},
                 "serve": {"batch": 2, "prompt_len": 16, "gen_len": 8}}
# limits for these sizes, from CPU readings of the program (largest of 8
# seeds) and of the fp8 control and the faults (smallest): program
# grad_gap_rms 6.3e-4, change_gap 5.6e-4; fp8 3.3e-3 and 2.0e-3; half batch
# 1.1e-2 and 0.17
SMOKE_LIMITS = {"train": {"grad_gap_rms": 1.4e-3, "change_gap": 1.2e-3}}


def smoke(workload: str) -> dict:
    """The cell as ``BENCHMARK.json`` defines it at smoke widths and sizes,
    with the limits for those sizes."""
    spec = run.resolve(run.load_json(run.ROOT / "BENCHMARK.json"), workload)
    spec["model"] = dict(spec["model"], **SMOKE_MODEL)
    spec["traffic"] = dict(spec["traffic"], **SMOKE_TRAFFIC[spec["traffic"]["runner"]])
    runner = spec["traffic"]["runner"]
    spec["checks"] = dict(spec["checks"], block_rows=1)
    if runner in SMOKE_LIMITS:
        spec["checks"]["limits"] = SMOKE_LIMITS[runner]
    return spec
