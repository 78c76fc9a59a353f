"""The exchange cell (`chipbench/pending/`) driven on 4 virtual CPU devices
at a small size: the
plain numpy alltoallv agrees with the program bit for bit; each fault
planted under the timed path, and the control (the program's own bf16 wire
codec), makes `correct` come out false."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


@pytest.fixture(scope="module")
def cases(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    root = str(tmp_path_factory.mktemp("checkout"))
    r = subprocess.run([sys.executable, os.path.join(HERE, "_cpu_cases.py"),
                        "exchange", root], capture_output=True, text=True,
                       timeout=600, env=env, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_program_matches_the_numpy_alltoallv(cases):
    assert cases["clean"]["correct"]
    assert cases["clean"]["checks"]["mismatched_elements"]["value"] == 0


@pytest.mark.parametrize("case", ["fault_stale", "fault_half", "fault_altered",
                                  "fault_no_exchange", "control_bf16"])
def test_fault_or_control_is_not_correct(cases, case):
    assert not cases[case]["correct"]
    assert cases[case]["checks"]["mismatched_elements"]["value"] > 0
