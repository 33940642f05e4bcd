"""Long-context causal LM — the sequence-parallel workload end to end.

Claims: the LM learns (loss falls on the structured synthetic stream); a
dp×sp mesh with ring attention and a dp×tp×sp mesh with Ulysses both train
step-for-step identically to full attention on a pure-dp mesh (parallelism
is invisible to the math); Megatron rules place every layer's projections.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import ps_tpu as ps
from ps_tpu.models import lm
from ps_tpu.models.blocks import make_attn_fn

VOCAB, D, HEADS, LAYERS, T, B = 64, 32, 4, 2, 32, 8


def _params():
    return lm.init_params(np.random.default_rng(0), vocab=VOCAB, d_model=D,
                          n_heads=HEADS, n_layers=LAYERS, max_len=T + 1)


def _train(mesh_shape, attn, steps=6, rules=None):
    ps.init(backend="tpu", mesh_shape=mesh_shape)
    ctx = ps.current_context()
    store = ps.KVStore(optimizer="adam", learning_rate=3e-3,
                       placement="sharded", partition_rules=rules)
    store.init(_params())
    attn_fn = make_attn_fn(attn, mesh=ctx.mesh)
    run = store.make_step(lm.make_loss_fn(n_heads=HEADS, attn_fn=attn_fn))
    sp = mesh_shape.get("seq", 1)
    sh = NamedSharding(ctx.mesh, P("data", "seq" if sp > 1 else None))
    losses = []
    for batch in lm.lm_batches(B, T, vocab=VOCAB, seed=1, steps=steps):
        placed = {k: jax.device_put(jnp.asarray(v), sh)
                  for k, v in batch.items()}
        loss, _ = run(placed)
        losses.append(float(loss))
    ps.shutdown()
    return losses


def test_lm_learns():
    losses = _train({"data": 8}, "full", steps=20)
    assert losses[-1] < losses[0] - 0.3, losses


@pytest.mark.parametrize("mesh,attn,rules", [
    ({"data": 2, "seq": 4}, "ring", None),
    ({"data": 2, "model": 2, "seq": 2}, "ulysses", lm.lm_partition_rules()),
], ids=["dp_sp_ring", "dp_tp_sp_ulysses"])
def test_parallelism_is_invisible(mesh, attn, rules):
    """Sequence/tensor parallel training == pure-dp full attention, step for
    step at the same global batch."""
    ref = _train({"data": 8}, "full")
    got = _train(mesh, attn, rules=rules)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_lm_rules_place_every_layer():
    ps.init(backend="tpu", mesh_shape={"data": 4, "model": 2})
    store = ps.KVStore(optimizer="sgd", learning_rate=0.1,
                       placement="replicated",
                       partition_rules=lm.lm_partition_rules())
    store.init(_params())
    spec = {k: v.sharding.spec for k, v in store._engine._params.items()}
    for i in range(LAYERS):
        assert spec[f"layer{i}/attn/qkv/kernel"] == P(None, "model")
        assert spec[f"layer{i}/attn/out/kernel"] == P("model", None)
        assert spec[f"layer{i}/mlp/in/kernel"] == P(None, "model")
        assert spec[f"layer{i}/mlp/out/kernel"] == P("model", None)
    ps.shutdown()
