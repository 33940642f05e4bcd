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

from ps_tpu.models import blocks
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
              "sdar": ("pallas", "logaddexp"),
              # JoyAI's rotation is a product of pairs: no kernel, no roll
              # of lanes, no rotation of halves
              "joyai": ("pallas", "roll(", "rotate_half"),
              # Granite-4.0-H's scan is token by token, its attention whole
              # rows under the model's own multiplier
              "granite_h": ("cumsum", "pallas"),
              # Qwen3-Next's rule is token by token, its rotation explicit
              # pairs, its experts a masked loop
              "qwen3_next": ("cumsum", "pallas", "rotate_half",
                             "ragged_dot"),
              # Ouro's passes and layers are Python loops: no kernel, no
              # scan
              "ouro": ("pallas", "lax.scan"),
              # Phi-4-mini-flash's scan is token by token (no cumulated sum,
              # no parallel scan), its attention an explicit mask
              "phi4flash": ("pallas", "cumsum", "associative_scan",
                            "cumprod")}
MODELS = ("lm", "olmoe", "lfm2", "kimi_linear", "nemotron_h", "trinity",
          "mellum", "sdar", "joyai", "granite_h", "qwen3_next", "ouro",
          "phi4flash")


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


# -- the latent attention two models share --------------------------------------

def _mla_as_kimi_had_it(lp, x, config, attn_fn):
    """``models/kimi_linear.py::mla_block`` as it stood before the block
    moved to ``models/blocks.py`` (commit 7b3f956), letter for letter."""
    from ps_tpu.models.blocks import rms_norm

    c = config
    b, s, _ = x.shape
    heads, nope, rope = (c.num_attention_heads, c.qk_nope_head_dim,
                         c.qk_rope_head_dim)

    def proj(name, h):
        return h @ lp[name]["kernel"].astype(h.dtype)

    q = proj("q", x).reshape(b, s, heads, nope + rope)
    latent = proj("kv_a", x)
    compressed, k_pe = jnp.split(latent, [c.kv_lora_rank], axis=-1)
    kv = proj("kv_b", rms_norm(compressed, lp["kv_norm"]["scale"],
                               c.rms_norm_eps)).reshape(b, s, heads, -1)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_pe[:, :, None, :], (b, s, heads, rope))], axis=-1)
    a = attn_fn(q, k, kv[..., nope:], causal=True)
    return a.reshape(b, s, -1) @ lp["out"]["kernel"].astype(x.dtype)


@pytest.mark.parametrize("attn", ["full", "flash"])
def test_the_shared_latent_block_is_kimis_to_the_bit_with_neither_option(attn):
    """``blocks.mla_block`` on a configuration with no ``q_lora_rank`` and no
    ``rope_theta`` (Kimi-Linear's) gives the outputs and every gradient of the
    block that model had, bit for bit, in the cell's bf16 over f32 leaves; the
    model's module holds no block of its own any more."""
    from ps_tpu.models import kimi_linear

    assert kimi_linear.mla_block is blocks.mla_block
    cfg = kimi_linear.KimiLinearConfig(
        hidden_size=64, num_attention_heads=4, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)
    assert not hasattr(cfg, "q_lora_rank") and not hasattr(cfg, "rope_theta")
    rng = np.random.default_rng(0)

    def w(*shape):
        return {"kernel": jnp.asarray(0.1 * rng.normal(size=shape),
                                      jnp.float32)}

    lp = {"q": w(64, 4 * 24), "kv_a": w(64, 40), "kv_b": w(32, 4 * 32),
          "kv_norm": {"scale": jnp.asarray(1 + 0.1 * rng.normal(size=(32,)),
                                           jnp.float32)},
          "out": w(64, 64)}
    x = jnp.asarray(rng.normal(size=(2, 128, 64)), jnp.bfloat16)
    attn_fn = make_attn_fn(attn)

    def run(block):
        def loss(lp, x):
            out = block(lp, x, cfg, attn_fn)
            return jnp.sum(jnp.sin(out.astype(jnp.float32))), out

        return jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))(lp, x)

    (_, out), grads = run(blocks.mla_block)
    (_, want), want_grads = run(_mla_as_kimi_had_it)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(want, np.float32))
    for g, r in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want_grads)):
        assert float(jnp.max(jnp.abs(r.astype(jnp.float32)))) > 0
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(r, np.float32))


# -- the Mamba-2 mixer two models share ----------------------------------------

def _mamba_as_nemotron_had_it(lp, x, config):
    """``models/nemotron_h.py::mamba_block`` as it stood before the block
    moved to ``models/blocks.py`` (commit cf89cf5), letter for letter."""
    from ps_tpu.models.blocks import rms_norm
    from ps_tpu.ops.gated_conv import conv_silu
    from ps_tpu.ops.ssd import ssd

    c = config
    b, s, _ = x.shape
    heads, groups, inner = c.mamba_num_heads, c.n_groups, c.mamba_inner
    projected = x @ lp["in_proj"]["kernel"].astype(x.dtype)
    z, xbc, dt = jnp.split(projected, [inner, inner + c.conv_dim], axis=-1)
    xbc = conv_silu(xbc, lp["conv"]["kernel"], lp["conv"]["bias"])
    xs, b_in, c_in = jnp.split(
        xbc, [inner, inner + groups * c.ssm_state_size], axis=-1)
    xs = xs.reshape(b, s, heads, c.mamba_head_dim)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
    y = ssd(xs, dt, -jnp.exp(lp["A_log"]),
            b_in.reshape(b, s, groups, -1), c_in.reshape(b, s, groups, -1),
            chunk=min(c.chunk_size, s))
    y = y.astype(jnp.float32) + lp["D"][:, None] * xs.astype(jnp.float32)
    y = y.reshape(b, s, inner) * jax.nn.silu(z.astype(jnp.float32))
    y = rms_norm(y.reshape(b, s, groups, -1),
                 lp["out_norm"]["scale"].reshape(groups, -1),
                 c.layer_norm_epsilon)
    return y.reshape(b, s, inner).astype(x.dtype) \
        @ lp["out_proj"]["kernel"].astype(x.dtype)


def _mixer_case(cfg, batch, seq, seed):
    """Seeded f32 leaves of one mixer at ``cfg``'s sizes and bf16 inputs
    ``[batch, seq, hidden_size]``: the cell's bf16 over f32 leaves."""
    rng = np.random.default_rng(seed)
    heads, inner, width = cfg.mamba_num_heads, cfg.mamba_inner, cfg.hidden_size

    def w(*shape):
        return jnp.asarray(0.1 * rng.normal(size=shape), jnp.float32)

    lp = {"in_proj": {"kernel": w(width, inner + cfg.conv_dim + heads)},
          "conv": {"kernel": w(cfg.conv_dim, 4), "bias": w(cfg.conv_dim)},
          "dt_bias": w(heads), "A_log": jnp.log(jnp.asarray(
              rng.uniform(1, 16, size=heads), jnp.float32)),
          "D": 1 + w(heads), "out_norm": {"scale": 1 + w(inner)},
          "out_proj": {"kernel": w(inner, width)}}
    return lp, jnp.asarray(rng.normal(size=(batch, seq, width)), jnp.bfloat16)


def _mixer_value_and_grads(block, cfg, lp, x):
    """((loss, output), (the leaves' gradients, ``x``'s)) of ``block``."""
    def loss(lp, x):
        out = block(lp, x, cfg)
        return jnp.sum(jnp.sin(out.astype(jnp.float32))), out

    return jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))(lp, x)


def test_the_shared_mamba_block_is_nemotrons_to_the_bit():
    """``blocks.mamba_block`` at Nemotron-H's sizes (a share of the heads on
    two B/C groups) gives the outputs and every gradient of the block that
    model had, bit for bit, in the cell's bf16 over f32 leaves; both models
    call the one block and neither holds a mixer of its own. Since PR 72
    the skip is ``repeat(D, head_dim)`` times the flat channels: the same
    products forward, so the output, ``x``'s gradient and every leaf that
    is a product with them stay to the bit. ``D``'s gradient is the same
    terms summed in another order (the tokens first, then a head's
    channels: 7e-7 of its largest entry seen), held to 1e-5. XLA's CPU
    fusions then sum two other leaves over the tokens in another order too
    (the taps' filter 1.3e-8 of its largest entry, the norm's scale 7e-8):
    those two are held to two f32 roundoffs of the largest entry, 2 ** -22,
    under which no fault of a gradient hides."""
    import inspect

    from ps_tpu.models import granite_h, nemotron_h

    assert granite_h.mamba_block is blocks.mamba_block
    for model in (granite_h, nemotron_h):
        source = inspect.getsource(model)
        assert "mamba_block(" in source and "ssd(" not in source
        assert "conv_silu(" not in source
    cfg = nemotron_h.NemotronHConfig(
        hidden_size=64, mamba_num_heads=4, mamba_head_dim=8, n_groups=2,
        ssm_state_size=16, chunk_size=32)
    lp, x = _mixer_case(cfg, 2, 128, seed=0)
    (_, out), grads = _mixer_value_and_grads(nemotron_h.mamba_block, cfg,
                                             lp, x)
    (_, want), want_grads = _mixer_value_and_grads(
        _mamba_as_nemotron_had_it, cfg, lp, x)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(want, np.float32))
    sums = [[tree.pop("D"), tree["conv"].pop("kernel"),
             tree.pop("out_norm")["scale"]]
            for tree in (grads[0], want_grads[0])]
    for g, r, tol in zip(*sums, (1e-5, 2.0 ** -22, 2.0 ** -22)):
        np.testing.assert_allclose(g, r, rtol=0,
                                   atol=tol * float(jnp.max(jnp.abs(r))))
    for g, r in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want_grads)):
        assert float(jnp.max(jnp.abs(r.astype(jnp.float32)))) > 0
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(r, np.float32))


def test_the_mixer_in_front_of_the_scans_kernels_takes_the_taps_kernels():
    """``blocks.mamba_block`` at the smallest shape the scan's kernels and
    the taps' both take (two heads of 64 on one B/C group over a state of 128
    are 384 channels through the taps, and 1,536 positions of them fill one
    block of 512 Ki elements; interpret mode here): the taps are their Mosaic
    calls in front of the scan's, and the output and every gradient agree with
    the block as it stood before PR 72, the XLA taps and the skip as 4-D
    math in front of the same scan. ``tests/test_gated_conv.py``'s
    tolerances for the taps' two forms in bf16: one rounding of the largest
    entry (2 ** -7) for the output and every gradient that passes through
    it, 1e-5 for the sums over the tokens that the kernels take block by
    block (the filter's, the bias's) and for ``D``'s, another order of one
    sum (seen: 6e-8, 1e-7 and 8e-7, everything else to the bit)."""
    from ps_tpu.models import nemotron_h
    from ps_tpu.ops import gated_conv, ssd_mosaic

    cfg = nemotron_h.NemotronHConfig(
        hidden_size=64, mamba_num_heads=2, mamba_head_dim=64, n_groups=1,
        ssm_state_size=128, chunk_size=128)
    lp, x = _mixer_case(cfg, 1, 1536, seed=72)
    assert ssd_mosaic.takes(jax.ShapeDtypeStruct((1, 1536, 2, 64), x.dtype),
                            jax.ShapeDtypeStruct((1, 1536, 1, 128), x.dtype),
                            cfg.chunk_size)
    assert gated_conv.path(jax.ShapeDtypeStruct((1, 1536, 384), x.dtype),
                           lp["conv"]["kernel"]) == "kernel"

    # the forward alone: the taps' call and the scan's, where one stood
    for block, calls in ((nemotron_h.mamba_block, 2),
                         (_mamba_as_nemotron_had_it, 1)):
        traced = jax.make_jaxpr(lambda lp, x: block(lp, x, cfg))(lp, x)
        assert str(traced).count("pallas_call") == calls
    (_, out), grads = _mixer_value_and_grads(nemotron_h.mamba_block, cfg,
                                             lp, x)
    (_, want), want_grads = _mixer_value_and_grads(
        _mamba_as_nemotron_had_it, cfg, lp, x)

    def rel(got, ref):
        got, ref = (np.asarray(t, np.float32) for t in (got, ref))
        return np.abs(got - ref).max() / np.abs(ref).max()

    assert rel(out, want) <= 2.0 ** -7
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for (leaf, g), r in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        name = jax.tree_util.keystr(leaf)
        a_sum = name.endswith(("['D']", "['conv']['kernel']",
                               "['conv']['bias']"))
        assert g.dtype == r.dtype and rel(g, r) <= (
            1e-5 if a_sum else 2.0 ** -7), (name, rel(g, r))


def test_the_interleaved_rotation_leaves_the_other_callers_trace_alone():
    """``rope`` without ``interleaved`` traces to what it traced to before
    the option: Mellum's, Trinity's, OLMoE's and SDAR's calls; with it, two
    rolls of the lanes and a select, and no strided slice."""
    x = jax.ShapeDtypeStruct((1, 256, 4, 64), jnp.bfloat16)
    halves = str(jax.make_jaxpr(lambda x: blocks.rope(x, 1e6))(x))
    assert "roll" not in halves and "select_n" not in halves
    assert halves == str(jax.make_jaxpr(
        lambda x: blocks.rope(x, 1e6, interleaved=False))(x))
    pairs = str(jax.make_jaxpr(
        lambda x: blocks.rope(x, 1e6, interleaved=True))(x))
    assert pairs.count("_roll_static") == 2 and "select_n" in pairs
    assert "strides=(1, 1, 1, 2)" not in pairs


@pytest.mark.parametrize("summed", [True, False])
def test_the_blocked_head_gives_the_whole_heads_loss_a_position(summed):
    """``blocks.blocked_head_nll`` (Mellum's blocked readout, moved here in
    PR 63 for Ouro's four weighted readouts): a position's loss [B, S], or
    with ``summed`` a block's sum, is the whole head's, value and gradient;
    weighted a position, its gradient is the weighted whole's; no [B, S, V]
    array stands in its trace."""
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(2, 16, 8)), jnp.float32)
    head = jnp.asarray(rng.normal(size=(8, 32)), jnp.float32)
    targets = jnp.asarray(rng.integers(0, 32, size=(2, 16)), jnp.int32)
    weights = jnp.asarray(rng.uniform(size=(2, 16)), jnp.float32)

    def whole(h, w):
        z = h @ w
        nll = jax.nn.logsumexp(z, -1) - jnp.take_along_axis(
            z, targets[..., None], -1)[..., 0]
        return jnp.sum(nll) if summed else jnp.sum(weights * nll)

    def blocked(h, w):
        out = blocks.blocked_head_nll(h, w, targets, 4, summed=summed)
        assert out.shape == ((4,) if summed else (2, 16))
        return jnp.sum(out) if summed else jnp.sum(weights * out)

    want = jax.value_and_grad(whole, (0, 1))(h, head)
    got = jax.value_and_grad(blocked, (0, 1))(h, head)
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert float(jnp.max(jnp.abs(g - r))) <= 1e-5 * float(
            jnp.max(jnp.abs(r)))
    text = str(jax.make_jaxpr(jax.grad(blocked, (0, 1)))(h, head))
    assert "f32[2,4,32]" in text and "[2,16,32]" not in text
    with pytest.raises(ValueError, match="do not tile"):
        blocks.blocked_head_nll(h, head, targets, 5)
