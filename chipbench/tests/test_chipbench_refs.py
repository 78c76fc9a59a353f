"""The plain references and the seeded generator they share with set-up."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import patterns  # noqa: E402
from chipbench.refs import alltoallv as a2a_ref  # noqa: E402


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 3, 2 ** 40 + 11])
def test_a_layer_of_the_program_tree_is_the_reference_layer(seed):
    """Set-up makes every leaf of the program's tree in one jitted call, the
    reference remakes one layer alone: the values must agree bit for bit."""
    import jax
    import jax.numpy as jnp
    from chipbench.refs import moe_lm
    m = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 2,
         "head_dim": 4, "num_experts": 4, "intermediate_size": 6,
         "vocab_size": 30, "vocab_pad_multiple": 16,
         "weights": {"moe/w_up": [0.25, 0.0], "ln1/scale": [0.1, 1.0]}}
    tree = {"slot0": {"moe": {"w_up": jax.ShapeDtypeStruct((3, 4, 8, 6), jnp.bfloat16)},
                      "ln1": {"scale": jax.ShapeDtypeStruct((3, 8), jnp.bfloat16)}}}
    made = moe_lm.program_params(m, seed, tree)
    for layer in range(3):
        for name, leaf in (("moe/w_up", made["slot0"]["moe"]["w_up"]),
                           ("ln1/scale", made["slot0"]["ln1"]["scale"])):
            want = np.asarray(moe_lm.leaf_f32(m, seed, name, layer))
            got = np.asarray(leaf[layer].astype(jnp.float32))
            assert (got.view(np.uint32) == want.view(np.uint32)).all(), name
    w = np.asarray(moe_lm.leaf_f32(m, seed, "ln1/scale", 0))
    assert abs(w.mean() - 1.0) < 0.2 and w.std() > 0


def test_numpy_alltoallv_by_hand():
    counts = np.array([[1, 2], [0, 1]])
    send = np.array([[[10], [20], [21]], [[30], [0], [0]]])
    got = a2a_ref.alltoallv(send, counts)
    assert [g[:, 0].tolist() for g in got] == [[10], [20, 21, 30]]


def test_mismatches_counts_elements_and_missing_rows():
    want = [np.ones((2, 3), np.float32), np.zeros((1, 3), np.float32)]
    recv = np.zeros((2, 2, 3), np.float32)
    recv[0] = 1.0
    assert a2a_ref.mismatches(recv, want) == 0
    recv[0, 1, 2] = np.nan
    recv[1, 0, 0] = -0.0             # differs from +0.0 bit for bit
    assert a2a_ref.mismatches(recv, want) == 2
    # rank 0 misses a row of 3 elements; the -0.0 of rank 1 still counts
    assert a2a_ref.mismatches(recv[:, :1], want) == 3 + 1


def test_hugetrace_counts_keep_their_signature():
    tr = {"pattern": "hugetrace", "pattern_seed": 7, "base_rows": 1000,
          "pattern_args": {"hot_ranks": [3], "hot_factor": 6.0},
          "mean_pair_bytes": 1 << 20}
    c = patterns.counts(tr, 4, 1024)
    assert abs(c.mean() - 1024) < 1
    assert c.sum(axis=0).argmax() == 3 and c.sum(axis=0)[3] > 4 * c.sum(axis=0)[0]
    assert (patterns.counts(tr, 4, 1024) == c).all()


@pytest.mark.parametrize("tokens", [1, 8, 37, 4096])
def test_reference_capacity_is_the_programs(tokens):
    import json
    from chipbench.refs import moe_lm
    from repro.configs.base import MoEConfig
    from repro.models.moe import MoEDispatchPlan
    m = json.load(open(os.path.join(ROOT, "chipbench/configs/olmoe-1b-7b.json")))["model"]
    moe = MoEConfig(n_experts=m["num_experts"], top_k=m["num_experts_per_tok"],
                    d_expert=m["intermediate_size"],
                    capacity_factor=m["capacity_factor"], dispatch="gspmd")
    plan = MoEDispatchPlan.build(moe, tokens, None, tile=m["capacity_tile"])
    assert moe_lm.capacity(m, tokens) == plan.capacity
