"""What the decoders share (``ps_tpu/models/blocks.py``) and what keeps them
apart: no model imports another model, each plain reference exists once and
imports nothing of the program, and the attention closure alone knows whether
K and V come grouped.
"""

import ast
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_tpu.models.blocks import make_attn_fn

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: each reference, and what its text may not hold beside ``ps_tpu``:
#: Nemotron-H's scan is token by token (no cumulated sum), Trinity's
#: attention an explicit band (no kernel)
REFERENCES = {"olmoe": (), "lfm2": (), "kimi": (), "nemotron_h": ("cumsum",),
              "trinity": ("pallas",),
              # Mellum's has no kernel, no mesh and no exchange
              "mellum": ("pallas", "shard_map", "all_to_all", "ragged_dot"),
              # SDAR's is one explicit mask: no kernel, no logsumexp merged
              "sdar": ("pallas", "logaddexp")}
MODELS = ("lm", "olmoe", "lfm2", "kimi_linear", "nemotron_h", "trinity",
          "mellum", "sdar")


@pytest.mark.parametrize("name", sorted(REFERENCES))
def test_a_plain_reference_imports_nothing_of_the_program(name):
    """``benchmark/families/<name>_reference.py`` is independent of the code
    it judges, and the one copy: the tests read it where it is."""
    with open(os.path.join(_REPO, "benchmark", "families",
                           f"{name}_reference.py")) as f:
        code = f.read().split('"""', 2)[2]       # behind the module docstring
    for word in ("ps_tpu",) + REFERENCES[name]:
        assert word not in code, word
    assert not os.path.exists(os.path.join(_REPO, "tests",
                                           f"{name}_reference.py"))


@pytest.mark.parametrize("name", MODELS)
def test_no_model_imports_another_model(name):
    """Of ``ps_tpu.models`` a decoder imports ``blocks`` only: a block two
    models need stands there, not in the model that needed it first."""
    with open(os.path.join(_REPO, "ps_tpu", "models", f"{name}.py")) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            assert node.level == 0, "a relative import"
            imported.update(f"{node.module}.{alias.name}"
                            for alias in node.names)
    of_models = {m for m in imported if m.startswith("ps_tpu.models")}
    assert all(f"{m}.".startswith("ps_tpu.models.blocks.")
               for m in of_models), of_models


@pytest.mark.parametrize("window", [None, 24])
def test_full_attention_takes_kv_at_their_own_head_count(window):
    """8 query heads on 2 K/V heads through ``make_attn_fn("full")`` is, to
    the bit, the same call on K and V repeated outside: values and all three
    gradients."""
    attn_fn = make_attn_fn("full")
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 64, 8, 16)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(2, 64, 2, 16)), jnp.float32)
            for _ in range(2))
    weights = jnp.asarray(rng.normal(size=q.shape), jnp.float32)

    def grouped(q, k, v):
        out = attn_fn(q, k, v, causal=True, window=window)
        return jnp.sum(out * weights), out

    def repeated(q, k, v):
        return grouped(q, *(jnp.repeat(t, 4, axis=2) for t in (k, v)))

    (_, out), grads = jax.value_and_grad(grouped, (0, 1, 2), has_aux=True)(
        q, k, v)
    (_, want), want_grads = jax.value_and_grad(
        repeated, (0, 1, 2), has_aux=True)(q, k, v)
    assert out.shape == q.shape
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    for g, w in zip(grads, want_grads):
        assert float(jnp.max(jnp.abs(w))) > 0
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("attn", ["ring", "ulysses"])
def test_a_sequence_parallel_closure_refuses_grouped_kv_by_name(attn):
    """'ring' and 'ulysses' take equal head counts only, and say so before
    any einsum does."""
    q = jnp.zeros((1, 8, 4, 8))
    k = v = jnp.zeros((1, 8, 2, 8))
    with pytest.raises(ValueError, match=f"make_attn_fn\\('{attn}'\\)"):
        make_attn_fn(attn)(q, k, v)
