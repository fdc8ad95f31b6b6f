"""The plain references against the program's own forward pass, on the CPU
at a small size, the program switched to float32: the same weights from
the seed, the same loss and the same gradients."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
import generator
import harness
from reference.common import POLICIES, init_params, layer_norms, seed_key

SEED = 2**31 + 101


@pytest.mark.parametrize("name", sorted(bench_tiny.TINY))
def test_reference_matches_program_in_float32(name):
    from repro.models import model as M
    from repro.models.blocks import RunConfig
    from repro.models.common import materialize

    cfg = bench_tiny.tiny_config(name)
    pcfg = harness.module("drivers", "train").program_config(cfg)
    assert pcfg.dtype == "float32"
    fam = harness.module("reference", cfg["family"])
    prog = materialize(M.model_specs(pcfg), seed_key(SEED))
    ref = init_params(fam.leaf_shapes(cfg), SEED)
    assert jax.tree_util.tree_structure(prog) == \
        jax.tree_util.tree_structure(ref)
    for a, b in zip(jax.tree_util.tree_leaves(prog),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    stream = generator.TokenStream({"kind": "zipf_documents",
                                    "exponent": 1.3, "doc_tokens": 65,
                                    "shard_docs": 8, "shared_ranks": 64},
                                   cfg["vocab_size"], SEED)
    (tokens, labels), = generator.train_rows(stream, 2, 64, 1)
    batch = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels)}
    run = RunConfig(attn_impl="dense", remat="none")
    pol = POLICIES["float32"]

    def ref_loss(p):
        return sum(fam.row_nll(p, batch["tokens"][r], batch["labels"][r],
                               cfg, pol) for r in range(2)) / tokens.size

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(
            lambda p: M.loss_fn(p, batch, pcfg, run)[0])(prog)
        lr, gr = jax.value_and_grad(ref_loss)(ref)
    assert abs(float(lp) - float(lr)) < 1e-5 * float(lr)
    np_, nr = layer_norms(gp), layer_norms(gr)
    assert np_.keys() == nr.keys()
    for k in nr:
        assert abs(np_[k] - nr[k]) <= 1e-4 * max(nr[k], 1e-6), k
