"""The exchange cell as BENCHMARK.json runs it (driver `exchange_request`)
on 4 virtual CPU devices at a small size, once clean and once with the
program's bf16 wire codec as the control.

    python chipbench/tests/_cpu_request_case.py

The last line printed is a JSON object `{case: {...}}`: for each case
`correct`, the checks, the end-to-end metrics, and the driver's `layer`
with the `a2a.wire_pad_share` reader's value beside it.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE]

from _cpu_cases import EXCHANGE_SMALL, SEED  # noqa: E402

CELL = "a2a.hugetrace.1mib"


def _case(harness, seed):
    res, out = harness.execute(CELL, seed, 0.1, False, accelerator=False,
                               overrides=EXCHANGE_SMALL)
    pad = harness.load_module("metrics", "a2a.wire_pad_share").read(
        {"layer": out.layer})
    lay = {k: (v.tolist() if hasattr(v, "tolist") else v)
           for k, v in out.layer.items()}
    return {"correct": res["correct"], "checks": res["checks"],
            "metrics": sorted(res["metrics"]), "layer": lay,
            "wire_pad_share": pad}


def main():
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import repro.core as core
    from chipbench import harness

    out = {"clean": _case(harness, SEED)}
    init = core.alltoallv_init
    core.alltoallv_init = lambda *a, **kw: init(
        *a, codec="bf16", error_tol=2.0 ** -8, **kw)
    try:
        out["control_bf16"] = _case(harness, SEED + 1)
    finally:
        core.alltoallv_init = init
    print(json.dumps(out))


if __name__ == "__main__":
    main()
