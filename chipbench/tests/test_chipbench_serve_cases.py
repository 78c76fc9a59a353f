"""The olmoe serving cell driven on the CPU at test widths (6 layers, 8
experts top-2, d_model 128): the program's served tokens lie within the
cell's limit of the float32 reference's best; each fault planted under the
timed path, and the float8 control, read above it."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


@pytest.fixture(scope="module")
def cases():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(HERE, "_cpu_cases.py"),
                        "serve"], capture_output=True, text=True,
                       timeout=600, env=env, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_program_within_the_limit_of_the_reference(cases):
    assert cases["clean"]["correct"]
    for c in cases["clean"]["checks"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("case", ["fault_stale", "fault_half", "fault_altered",
                                  "control_fp8_1", "control_fp8_2",
                                  "control_fp8_3"])
def test_fault_or_control_is_not_correct(cases, case):
    assert not cases[case]["correct"]
    assert any(c["value"] > c["limit"] for c in cases[case]["checks"].values())
