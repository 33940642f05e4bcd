"""Kernels of the main path compiled for a described TPU v5e at the widths
the benchmark runs them at: what Mosaic refuses (a tile over its VMEM, an
index map it cannot lower) shows here and costs no chip time. Nothing runs,
so nothing here says a word about values or time. One file, and the
topology described inside a fixture: only one process may hold libtpu, and
only the worker that is given this file loads it.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ps_tpu.ops import flash_attention, grouped_matmul, moe, ssd_mosaic
from ps_tpu.ops.gated_conv import (conv_silu, conv_silu_kernel,
                                   gated_short_conv)
from ps_tpu.ops.gated_conv import path as taps_path
from ps_tpu.ops.kda import kda, path
from ps_tpu.ops.ssd import ssd


@pytest.fixture(scope="module")
def described_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:1x1",
            chips_per_host_bounds=(1, 1, 1))
    except Exception as e:
        pytest.skip(f"no v5e topology can be described here: {e}")
    return topo.devices[0]


@pytest.fixture
def one_chip(described_chip):
    """The sharding of every argument, and for the length of the test the
    described chip as ``jax.default_device``: what the kernels ask whether
    they go through Mosaic (``ops/mosaic.py``), and what every cache of
    traces keys, so a trace another test made for the CPU is not served.
    Nothing can be made or run under it, only described and compiled: a
    key, too, is made inside the ``jax.eval_shape`` that wants it."""
    with jax.default_device(described_chip):
        yield SingleDeviceSharding(described_chip)


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    and cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _described(sharding, tree):
    """``tree``'s leaves as shapes on the described chip: nothing is made."""
    return jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sharding), tree)


def _written(text: str):
    """The result types of a compiled program's instructions that write an
    array: every instruction outside the computations a ``fusion`` calls
    (what stands inside one lives in registers and VMEM)."""
    fused = set(re.findall(r" fusion\([^\n]*?calls=(%[\w.\-]+)", text))
    types, inside = [], False
    for line in text.splitlines():
        if not line.startswith(" "):  # a computation opens or closes
            inside = line.split(" ")[0] in fused
        elif not inside and " = " in line:
            result = re.match(r"(.*?)\s[\w\-]+\(", line.split(" = ", 1)[1])
            types.append(result.group(1) if result else "")
    return types


def _mosaic_calls(text: str):
    """A compiled program's Mosaic calls: the rotation's (``ops/rope.py``
    names its kernels ``rope`` and ``rope_transposed``) and the others."""
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    rotations = [line for line in calls if "rope" in line.split(" = ")[0]]
    return rotations, [line for line in calls if line not in rotations]


#: [B, S, query heads, K/V heads, head dim (, the values' own)], causal,
#: Mosaic calls of the gradient: the five cells' calls. The forward, dk / dv
#: and dq; at BERT's shape one backward tile spans the sequence and one call
#: gives all three. Kimi's latent attention has keys of 192 and values of 128;
#: Nemotron-H's share is 4 query heads on 1 K/V head
CALLS = {"lfm2-24b-a2b.s8192.zipf": ((2, 8192, 32, 8, 64), True, 3),
         "olmoe-1b-7b.s4096.zipf": ((2, 4096, 16, 16, 128), True, 3),
         "bert-base.s512.flash": ((32, 512, 12, 12, 64), False, 2),
         "kimi-linear-48b-a3b.s8192.b1.zipf":
             ((1, 8192, 32, 32, 192, 128), True, 3),
         "nemotron-3-super-120b-a12b.s8192.b1.zipf":
             ((1, 8192, 4, 1, 128), True, 3),
         # Trinity-Mini's 32 query heads on 4 K/V heads at 16,384: the one
         # layer that sees every earlier key, and the four that see WINDOWS'
         "trinity-mini.s16384.b1.zipf, full":
             ((1, 16384, 32, 4, 128), True, 3),
         "trinity-mini.s16384.b1.zipf, windowed":
             ((1, 16384, 32, 4, 128), True, 3),
         # Mellum's three layers of four that see 1,024 keys of 8,192
         "mellum2-12b-a2.5b.s8192.b1.zipf.x4, windowed":
             ((1, 8192, 32, 4, 128), True, 3),
         # Qwen3-Next's gated attention: keys and values of 256 alike, the
         # widest head and the widest group (eight query heads a K/V head)
         "qwen3-next-80b-a3b.s8192.b1.zipf":
             ((1, 8192, 16, 2, 256), True, 3)}
#: the window of a cell's call, where it has one: the band step
#: (``ops/flash_attention.py``), a query block against the slab of key
#: blocks it sees passed as so many operands, at the block and sub-block the
#: rules choose
WINDOWS = {"trinity-mini.s16384.b1.zipf, windowed": 2048,
           "mellum2-12b-a2.5b.s8192.b1.zipf.x4, windowed": 1024}


@pytest.mark.parametrize("cell", sorted(CALLS))
def test_flash_forward_and_backward_compile_at_the_cells_shapes(
        cell, one_chip, no_compile_cache):
    (b, s, h, h_kv, d, *d_v), causal, calls = CALLS[cell]

    def arg(heads, width=d):
        return jax.ShapeDtypeStruct((b, s, heads, width), jnp.bfloat16,
                                    sharding=one_chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=causal,
                              window=WINDOWS.get(cell))
        return jnp.sum(out.astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        arg(h), arg(h_kv), arg(h_kv, *d_v)).compile().as_text()
    # the backward is Mosaic kernels too, and no loop of XLA's is left
    assert text.count('custom_call_target="tpu_custom_call"') == calls
    assert " while(" not in text


@pytest.mark.parametrize("strict", [False, True],
                         ids=["clean-queries", "noised-queries"])
def test_the_edged_flash_calls_compile_at_sdars_shape(strict, one_chip,
                                                      no_compile_cache):
    """``sdar-30b-a3b.s8192.b1.zipf.bd4``'s two calls a layer at [1, 8192,
    32 on 4, 128] (a step's batch of two): the edge a block of 4 wide, and,
    for the noised queries, the strict edge with the logsumexp as a second
    output whose cotangent the backward takes: three Mosaic calls each, no
    loop of XLA's."""
    def arg(heads):
        return jax.ShapeDtypeStruct((1, 8192, heads, 128), jnp.bfloat16,
                                    sharding=one_chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, edge_block=4,
                              strict_edge=strict, return_lse=strict)
        if not strict:
            return jnp.sum(out.astype(jnp.float32))
        out, lse = out
        return jnp.sum(out.astype(jnp.float32)) + jnp.sum(
            jnp.logaddexp(lse, 0.0))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        arg(32), arg(4), arg(4)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert " while(" not in text


def test_the_own_block_kernels_engage_in_sdars_step(one_chip,
                                                   no_compile_cache):
    """``sdar-30b-a3b.s8192.b1.zipf.bd4``: value and gradient of the loss
    ``KVStore.make_step`` differentiates at the configuration's published
    widths and [1, 8192] tokens, one of its six layers (they are alike; the
    step compiles in three quarters of a minute with one). ``ops/own_block.py::path`` is
    static, so what says the kernels engage is the compiled program: under
    ``ps.attn/inblock`` three Mosaic calls a layer (the forward, the forward
    again in the layer's recomputation, the backward) and no f32 array of q's
    size, ``[.., 8192, 32, 128]`` whole or cut in blocks of four, written by
    a convert, a reduction or anything else; the flash calls stay six a
    layer. And what says the layer's names (``sdar.PRODUCTS_KEPT``) engaged
    under the chip's compiler: a product that gives ``bf16[2, 8192, 4096]``
    is q's projection (``[.., 2048] x [2048, 4096]``) or the out
    projection's cotangent for its input, and the step holds one of each a
    layer, the first in the forward pass and none in the recomputation (the
    policy that listed the flash calls' residuals alone held q's twice);
    the rotation's Mosaic calls are two forward and two transposed, none
    again."""
    import json
    import os

    from ps_tpu.models import sdar

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "sdar-30b-a3b.json")) as f:
        cfg = sdar.SdarConfig.from_dict(
            {**json.load(f), "num_hidden_layers": 1})

    on_chip = functools.partial(_described, one_chip)

    params = on_chip(jax.eval_shape(
        lambda: sdar.init_params(jax.random.key(0), cfg)))
    ids = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one_chip)
    weights = jax.ShapeDtypeStruct((1, 8192), jnp.float32, sharding=one_chip)
    loss = sdar.make_loss_fn(cfg, attn="flash")
    text = jax.jit(jax.value_and_grad(loss, has_aux=True)).lower(
        params, {"ids": ids, "noised_ids": ids, "weights": weights}
    ).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    own = [line for line in calls if "ps.attn/inblock" in line]
    assert len(own) == 3 * cfg.num_hidden_layers
    assert sum("own_block_backward" in line for line in own) \
        == cfg.num_hidden_layers
    assert sum("ps.attn/full" in line for line in calls) \
        == 6 * cfg.num_hidden_layers
    under = [line for line in text.splitlines() if "ps.attn/inblock" in line]
    assert not [line for line in under if re.search(
        r"f32\[(\d+,)*(8192,32,128|2048,4,4,8,128|8192,4,8,128)\]", line)]
    q_wide = [line for line in text.splitlines() if re.search(
        r"= bf16\[2,8192,4096\]\S* convolution\(", line)]
    assert len(q_wide) == 2 * cfg.num_hidden_layers
    assert sum("transpose(" not in line for line in q_wide) \
        == cfg.num_hidden_layers
    assert not [line for line in q_wide + calls
                if "rematted_computation" in line and "/inblock/" not in line]


def test_gated_conv_compiles_at_the_cells_shape(one_chip, no_compile_cache):
    bcx = jax.ShapeDtypeStruct((2, 8192, 3 * 2048), jnp.bfloat16,
                               sharding=one_chip)
    w = jax.ShapeDtypeStruct((2048, 3), jnp.float32, sharding=one_chip)

    def loss(bcx, w):
        return jnp.sum(gated_short_conv(bcx, w).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(bcx, w).compile()
    # XLA keeps f32 intermediates of [tokens, D] between the two fusions
    # (873 MB where bcx is 201): the room PERF.md section 7 names
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


#: channels and whether there is a bias: the three cells' calls on [1, 8192, C]
TAPS = {"granite-4.0-h-micro.s8192.b1.zipf": (4352, True),
        "kimi-linear-48b-a3b.s8192.b1.zipf": (4096, False),
        "nemotron-3-super-120b-a12b.s8192.b1.zipf": (1280, True)}


@pytest.mark.parametrize("cell", sorted(TAPS))
def test_the_taps_shift_in_vmem_at_the_cells_shapes(cell, one_chip,
                                                    no_compile_cache):
    """``conv_silu`` at ``bf16[1, 8192, C]``, four taps: the shapes take the
    kernels, the forward is one Mosaic call and so is the gradient (the
    forward's output is not asked for, so XLA drops that call), no shifted
    f32 copy of ``[S, C]`` stands in either entry computation (the plain
    form's gradient held seven ``f32[1, 81xx, 4352]`` arrays and 571 MB of
    temporaries; its forward three and 428 MB) and the temporaries are under
    64 MB."""
    channels, bias = TAPS[cell]

    def arg(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (arg(1, 8192, channels, dtype=jnp.bfloat16), arg(channels, 4),
            arg(channels) if bias else None)
    assert taps_path(*args[:2]) == "kernel"

    def forward(x, w, b):
        return conv_silu_kernel(x, w, b)

    def loss(x, w, b):
        return jnp.sum(forward(x, w, b).astype(jnp.float32))

    wrt = (0, 1, 2) if bias else (0, 1)
    for fn in (forward, jax.grad(loss, argnums=wrt)):
        compiled = jax.jit(fn).lower(*args).compile()
        text = compiled.as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 1
        assert " while(" not in text
        assert not re.search(rf"f32\[1,81\d\d,{channels}\]", text)
        assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


@pytest.mark.parametrize("cell", sorted(c for c in TAPS if "kimi" not in c))
def test_the_taps_xla_form_writes_no_shifted_copy(cell, one_chip,
                                                  no_compile_cache):
    """``conv_silu`` (no word of the kernels: the XLA form, which
    ``mamba_block`` called until PR 72) at ``bf16[1, 8192, C]``: the pad in
    ``x``'s own dtype and the slices cut from it fuse, so the forward keeps
    nothing beside ``x`` and ``y`` (428 MB of shifted f32 copies before PR 57)
    and the gradient at most ``dz`` in f32 (571 MB before), and neither holds
    a Mosaic call."""
    channels, _ = TAPS[cell]

    def arg(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (arg(1, 8192, channels, dtype=jnp.bfloat16), arg(channels, 4),
            arg(channels))

    def loss(x, w, b):
        return jnp.sum(conv_silu(x, w, b).astype(jnp.float32))

    whole = 8192 * channels
    for fn, room in ((conv_silu, 0), (jax.grad(loss, (0, 1, 2)), 4 * whole)):
        compiled = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" not in compiled.as_text()
        assert compiled.memory_analysis().temp_size_in_bytes \
            <= room + 2 ** 20
        # x and y once (and dy, dx and dz's two passes), not a copy a tap
        passes = compiled.cost_analysis()["bytes accessed"] / (2 * whole)
        assert passes < (2.1 if room == 0 else 6.1)


def test_chunked_kda_compiles_at_the_cells_shape(one_chip, no_compile_cache):
    """``ops/kda.py`` at [1, 8192, 32, 128], forward and backward: the shape
    takes the Mosaic kernels (``kda.path``), two calls in the gradient (the
    forward that keeps each chunk's entering state and inverse, and the
    backward: the op has no ``jax.checkpoint`` of its own, so nothing runs
    twice) and no loop of XLA's:
    the state is carried in VMEM along the grid. Between the two calls live
    the states (268 MB) and the inverses, where the plain form's four
    ``while`` loops kept 600 MB of the chunks' internals."""
    def arg(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    wide = (1, 8192, 32, 128)
    args = (arg(*wide), arg(*wide), arg(*wide),
            arg(*wide, dtype=jnp.float32),
            arg(*wide[:3], dtype=jnp.float32))
    assert path(*args[:3], 64) == "kernel"

    def loss(q, k, v, g, beta):
        return jnp.sum(kda(q, k, v, g, beta).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *args).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert " while(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 2 ** 30


def test_the_scalar_decay_rule_compiles_as_qwen3_next_calls_it(
        one_chip, no_compile_cache):
    """``ops/kda.py`` as ``models/qwen3_next.py::gdn_block`` calls it: q and
    k [1, 8192, 16, 128] read by 32 value heads, ``g`` [1, 8192, 32] one
    decay a head. The operands' own shapes take the scalar-decay kernels
    (``path``), the gradient holds their two calls and no loop of XLA's,
    both calls read q and k at 16 heads ([1, 8192, 2048]) and the decay a
    row a head, the program holds no decay a channel (no f32 array of
    [8192, 32, 128] in either direction), no key head repeated for its
    readers and none of autodiff's sums back, and the five gradients come
    back at the operands' own shapes. Temporaries 0.50e9 B (the kept states
    and inverses are 0.34e9 of them), where the per-channel kernels on
    broadcast operands stood under 3 x 2^30."""
    def arg(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    keys, values = (1, 8192, 16, 128), (1, 8192, 32, 128)
    args = (arg(*keys), arg(*keys), arg(*values),
            arg(*values[:3], dtype=jnp.float32),
            arg(*values[:3], dtype=jnp.float32))
    assert path(*args[:3], 64, args[3]) == "scalar_kernel"

    def loss(q, k, v, g, beta):
        return jnp.sum(kda(q, k, v, g, beta).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *args).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2
    for call in calls:
        read = re.findall(r"(\w+\[[\d,]+\])\{", call.split(
            "operand_layout_constraints={")[1])
        assert read[:3] == ["bf16[1,8192,2048]"] * 2 + ["bf16[1,8192,4096]"]
        assert read[3:5] == ["f32[1,32,128,1,64]"] * 2
    assert " while(" not in text
    for gone in ("f32[1,8192,32,128]", "f32[1,8192,4096]",
                 "bf16[1,8192,16,2,128]", "f32[1,8192,16,2,128]"):
        assert gone not in text, gone
    grads = jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2, 3, 4)), *args)
    assert [x.shape for x in grads] == [a.shape for a in args]
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9


def test_the_kda_mixer_keeps_the_kernels_residuals_at_the_cells_shape(
        one_chip, no_compile_cache):
    """``models/kimi_linear.py::_mixer`` at the cell's shape ([1, 8192,
    4096] projections, 32 heads), value and gradient: its checkpoint keeps
    the rule's output, states and inverses by name (``ops/kda.py::KEPT``),
    so the program holds the rule's forward call once and its backward call,
    where a policy-less checkpoint holds a second forward (the value is asked
    for so that XLA cannot drop the first), and nine calls of the taps
    (``ops/gated_conv.py``; q, k and v: forward, the forward again under the
    checkpoint, backward). Temporaries 1.28e9 B, where the taps' plain form
    stood at 2.83e9: the three kept arrays are 0.40e9 of them, the rest the
    recomputed decays and gates in f32."""
    from ps_tpu.models import kimi_linear

    heads, width, rank, tokens = 32, 128, 128, (1, 8192)

    def arg(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def projection(n):
        return arg(*tokens, n, dtype=jnp.bfloat16)

    wide = heads * width
    projected = (projection(wide),) * 3 + (projection(rank),) * 2 \
        + (projection(heads),)
    weights = {**{f"{n}_conv": arg(wide, 4) for n in "qkv"},
               "f_b": {"kernel": arg(rank, wide)},
               "g_b": {"kernel": arg(rank, wide)}, "dt_bias": arg(wide),
               "A_log": arg(heads), "out_norm": {"scale": arg(width)}}

    def loss(projected, weights):
        return jnp.sum(kimi_linear._mixer(projected, weights, heads, 1e-5)
                       .astype(jnp.float32))

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        projected, weights).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2 + 9
    assert " while(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 2 ** 29


def _scan_gradient(one_chip, heads, width, state, chunk, dtype):
    """``ops/ssd.py::ssd``'s gradient at 8,192 tokens of ``heads`` heads of
    ``width`` on one B/C group, compiled: (its text, its temporaries'
    bytes). ``x`` enters and its cotangent leaves as the mixer has them,
    [1, 8192, heads * width]: a [.., heads, width] argument of a program has
    a tiled layout of its own, which a copy would have to undo."""
    def arg(*shape, dtype=dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (arg(1, 8192, heads * width),
            arg(1, 8192, heads, dtype=jnp.float32),
            arg(heads, dtype=jnp.float32), arg(1, 8192, 1, state),
            arg(1, 8192, 1, state))

    def loss(x, dt, a, b, c):
        return jnp.sum(ssd(x.reshape(1, 8192, heads, width), dt, a, b, c,
                           chunk=chunk).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *args).compile()
    return compiled.as_text(), compiled.memory_analysis().temp_size_in_bytes


def _entering_states(heads, width=64, state=128):
    """Bytes of the f32 states that entered each of the kernels' chunks of
    8,192 tokens: what the forward call keeps for the backward one."""
    return 8192 // ssd_mosaic.CHUNK * heads * width * state * 4


def test_chunked_ssd_compiles_at_the_cells_shape(one_chip, no_compile_cache):
    """``ops/ssd.py`` at the Nemotron cell's share, 16 heads of 64 on one B/C
    group of state 128 in chunks of 128, forward and backward: the two Mosaic
    calls of ``ops/ssd_mosaic.py`` (a silent fall back to the XLA form fails
    here), no ``while`` over the chunks and no [128, 128] decay matrix in
    HBM. The temporaries are at most the f32 states that entered each chunk
    (33.6 MB; the compile counts 0 B here, the states in a buffer it does not
    count) and the steps re-laid, where the XLA form held 2**30 B."""
    text, temp = _scan_gradient(one_chip, 16, 64, 128, 128, jnp.bfloat16)
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert " while(" not in text
    assert not re.search(r"f32\[[0-9,]*128,128\]", text)
    assert temp <= _entering_states(16) + 2 ** 22


def test_the_scan_compiles_at_granites_shape(one_chip, no_compile_cache):
    """``ops/ssd.py`` at the Granite cell's whole mixer, 64 heads of 64 on
    one B/C group of state 128 in chunks of 256, forward and backward: the
    same two Mosaic calls (eight blocks of eight heads a chunk), no
    ``while``, no f32 [.., 256, 256] array over all heads and no copy of an
    array as large as ``x`` into a second layout. The temporaries are the
    entering states (134.2 MB) and the steps re-laid for the blocks of eight
    heads with their cotangents (67.6 MB seen: 2 MB each by their shapes,
    33.6 as the chip pads a last axis of eight to 128 lanes), where the XLA
    form's gradient held 0.49e9 B."""
    text, temp = _scan_gradient(one_chip, 64, 64, 128, 256, jnp.bfloat16)
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert " while(" not in text
    assert not re.search(r"f32\[[0-9,]*256,256\]", text)
    assert not re.search(r"\[1,8192,(4096|64,64)\]\S* copy\(", text)
    assert _entering_states(64) <= temp <= _entering_states(64) + 2 ** 27


#: (model width, heads of 64 on one B/C group of state 128, chunk): the two
#: cells whose layers run ``models/blocks.py::mamba_block``
MIXERS = {"granite-4.0-h-micro.s8192.b1.zipf": (2048, 64, 256),
          "nemotron-3-super-120b-a12b.s8192.b1.zipf": (4096, 16, 128)}


@pytest.mark.parametrize("cell", sorted(MIXERS))
def test_the_mixer_is_six_mosaic_calls_and_no_copy_at_the_cells_shapes(
        cell, one_chip, no_compile_cache):
    """The gradient of two layers of ``mamba_block`` at ``bf16[1, 8192, D]``,
    each under a ``jax.checkpoint`` with a residual around it as both models
    run them: the scan takes its kernels at these shapes and the taps
    theirs (``ssd_mosaic.takes``, ``gated_conv.path``), so a layer holds six
    Mosaic calls: the taps' forward and the scan's, both again in the
    recomputation, and the two backward calls. No array as large as the
    scan's ``x`` is copied into a second layout. With the XLA taps and the
    skip as 4-D math over ``[.., heads, 64]`` a layer of this program held
    four such copies (``bf16[1,8192,4096]{1,2,0}`` in front of the scan's
    backward call, two back to ``{2,1,0}`` behind it,
    ``f32[1,8192,4096]{1,2,0}`` under the gate's product: 36 in the Granite
    cell's step, 7.9 ms; one mixer alone compiles without them, two in a row
    do not)."""
    from ps_tpu.models import blocks

    width, heads, chunk = MIXERS[cell]
    inner, state = heads * 64, 128

    def arg(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lp = {"in_proj": {"kernel": arg(width, 2 * inner + 2 * state + heads)},
          "conv": {"kernel": arg(inner + 2 * state, 4),
                   "bias": arg(inner + 2 * state)},
          "dt_bias": arg(heads), "A_log": arg(heads), "D": arg(heads),
          "norm": arg(width), "out_norm": {"scale": arg(inner)},
          "out_proj": {"kernel": arg(inner, width)}}

    @jax.checkpoint
    def layer(lp, x):
        return x + blocks.mamba_block(
            lp, blocks.rms_norm(x, lp["norm"], 1e-5), heads=heads,
            head_dim=64, groups=1, state=state, chunk=chunk, eps=1e-5)

    def loss(lp, x):
        return jnp.sum(layer(lp, layer(lp, x)).astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, (0, 1))).lower(
        lp, arg(1, 8192, width, dtype=jnp.bfloat16)).compile().as_text()
    _, calls = _mosaic_calls(text)
    assert len(calls) == 12
    assert sum("ps.mamba/conv" in line for line in calls) == 6
    assert sum("ps.mamba/ssd" in line for line in calls) == 6
    assert not re.search(rf"\[1,8192,({inner}|{heads},64)\]\S* copy\(", text)


def test_a_rehearse_shape_takes_the_xla_form(one_chip, no_compile_cache):
    """What the kernels do not take (``ops/ssd_mosaic.py::takes``: a
    ``rehearse`` configuration's heads of 16 on a state of 16 in chunks of 32,
    in f32) compiles as the XLA form, the state carried by ``while`` loops:
    the case that shows the two tests above would see a fall back."""
    text, _ = _scan_gradient(one_chip, 4, 16, 16, 32, jnp.float32)
    assert 'custom_call_target="tpu_custom_call"' not in text
    assert " while(" in text


#: (tokens, width of the rows, width of an expert, held, router width,
#: picks, activation), the window's rows, and the temp bytes the same block
#: compiled to before PR 40 (its row buffers 65,536 long), read once from
#: commit 83c8eb2: the bound
EXPERT_BLOCKS = {
    "lfm2-24b-a2b.s8192.zipf":
        ((16384, 2048, 1536, 8, 64, 4, "swiglu"), 24576, 1197941248),
    "kimi-linear-48b-a3b.s8192.b1.zipf":
        ((8192, 2304, 1024, 8, 256, 8, "swiglu"), 6144, 1064713216),
    "nemotron-3-super-120b-a12b.s8192.b1.zipf":
        ((8192, 1024, 2688, 8, 512, 22, "relu2"), 8704, 994259456)}


#: rows, a row's width, an expert's width, groups: the grouped matmuls of
#: the six expert cells (Mellum's a source's buffer, 16 experts a chip)
GROUPED_MATMULS = {
    "mellum2-12b-a2.5b.s8192.b1.zipf.x4": (49152, 2304, 896, 16),
    "olmoe-1b-7b.s4096.zipf": (65536, 2048, 1024, 64),
    "lfm2-24b-a2b.s8192.zipf": (24576, 2048, 1536, 8),
    "trinity-mini.s16384.b1.zipf": (49152, 2048, 1024, 16),
    "nemotron-3-super-120b-a12b.s8192.b1.zipf": (8704, 1024, 2688, 8),
    "kimi-linear-48b-a3b.s8192.b1.zipf": (6144, 2304, 1024, 8)}


@pytest.mark.parametrize("cell", sorted(GROUPED_MATMULS))
def test_grouped_matmul_and_its_gradients_compile_at_the_cells_shapes(
        cell, one_chip, no_compile_cache):
    """``gmm`` into an expert and out of it again, forward, the rows'
    gradient and the stacks' gradient of each, at the tiles ``tiles(..)``
    chooses: a choice that overflows VMEM fails here, not on the chip."""
    m, d, f, e = GROUPED_MATMULS[cell]

    def run(rows, up, down, sizes, g):
        out, pull = jax.vjp(
            lambda rows, up, down: grouped_matmul.gmm(
                grouped_matmul.gmm(rows, up, sizes), down, sizes),
            rows, up, down)
        return out, pull(g)

    def arg(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(run).lower(
        arg(m, d), arg(e, d, f), arg(e, f, d), arg(e, dtype=jnp.int32),
        arg(m, d)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 6
    assert "ragged-dot" not in text and " while(" not in text


@pytest.mark.parametrize("cell", sorted(EXPERT_BLOCKS))
def test_a_shares_expert_block_moves_a_window_of_rows(
        cell, one_chip, no_compile_cache):
    """Route, dispatch, the held experts and combine of ``ops/moe.py`` at
    the three share cells' shapes, forward and backward: nothing 65,536 rows
    long and as wide as a row is left (no gather, no ``where``, no buffer of
    the experts), the windows behind the first are a ``while`` loop that a
    step with no overflow never enters (the backward's: the gradient of this
    sum needs no forward value, so the forward's loop is gone), and the
    block needs no more temp bytes than it did with whole buffers."""
    (t, d, f, held, width, top_k, activation), rows, parent_temp = (
        EXPERT_BLOCKS[cell])
    gated = activation == "swiglu"

    def window_of(routing, x, gate, down, *up):
        buffer = moe.dispatch(x, routing)
        buffer = moe.expert_ffn(
            buffer, gate, *(up or (None,)), down, routing.group_sizes,
            activation=activation,
            expected_rows=None if gated else buffer.shape[0])
        return moe.combine(buffer, routing)

    def loss(x, router, *stacks):
        routing = moe.route(x, router, top_k, renormalize=True,
                            scoring="sigmoid", held=(0, held))
        assert routing.window.shape == (rows,)
        out = moe.over_windows(window_of, routing, x, *stacks)
        return jnp.sum(out.astype(jnp.float32))

    def arg(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (arg(t, d), arg(d, width, dtype=jnp.float32), arg(held, d, f),
            arg(held, f, d)) + ((arg(held, d, f),) if gated else ())
    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(len(args))))).lower(
        *args).compile()
    text = compiled.as_text()
    whole = t * min(top_k, held)
    assert whole == 65536
    tall = re.findall(rf"\w+\[{whole},\d{{3,}}\]", text)
    assert not tall, sorted(set(tall))
    assert f"bf16[{rows},{d}]" in text and f"bf16[{rows},{f}]" in text
    assert text.count(" while(") == 1 and " conditional(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes <= parent_temp


@pytest.mark.parametrize("batch,seq", [(32, 512), (128, 128)])
def test_labelled_mlm_head_compiles_at_the_cells_shapes(batch, seq, one_chip,
                                                        no_compile_cache):
    """BERT-base's embeddings and MLM head (no encoder layer) through
    ``make_mlm_loss_fn``, forward and backward, at the BERT cells' batches
    and the published vocabulary: the head runs on a quarter of the 16,384
    positions a trip, so nothing as wide as the vocabulary has more rows
    than that (the parent's logits were ``f32[16384, 30522]``, 2 GB); the
    later trips are ``while`` loops the labels' count bounds, never a
    second head at full size."""
    import numpy as np

    from ps_tpu.models.bert import (BertConfig, BertMLM, head_groups,
                                    make_mlm_loss_fn)

    model = BertMLM(BertConfig(num_layers=0))
    vocab = model.cfg.vocab_size

    def described(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(described, jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((2, seq), jnp.int32),
                           jnp.ones((2, seq), jnp.int32))["params"]))
    batch_shapes = {k: jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                            sharding=one_chip)
                    for k in ("input_ids", "labels", "attention_mask")}
    text = jax.jit(jax.value_and_grad(make_mlm_loss_fn(model))).lower(
        params, batch_shapes).compile().as_text()
    per_group, rows = head_groups(batch, seq)
    head_rows = batch // per_group * rows
    assert head_rows == 4096
    wide = {tuple(int(d) for d in dims.split(","))
            for dims in re.findall(r"\w+\[([0-9,]+)\]", text)
            if str(vocab) in dims.split(",")}
    assert (16, 256, vocab) in wide  # a trip's logits
    too_tall = [d for d in wide if np.prod(d) // vocab > head_rows]
    assert not too_tall, too_tall
    assert text.count(" while(") == 2 and " conditional(" not in text


def test_joyais_step_compiles_at_the_cells_shape(one_chip, no_compile_cache):
    """``joyai-llm-flash.s8192.b1.zipf``: value and gradient of the loss
    ``KVStore.make_step`` differentiates, at the configuration's published
    widths and [1, 8192] tokens. Eighteen Mosaic flash calls, three for each
    of the six latent layers (the module's the sixth; each layer's checkpoint
    keeps the forward call's output and logsumexp, so none runs twice), each
    written out: the only loops are the expert layers' windows behind the
    first, forward and backward, which a step with no overflow never enters.
    Parameters and their gradients are 5.4e9 B of the program; the rest, the
    temporaries, stays under 3.5e9 B: two [8192, 16160] f32 logit arrays are
    not live at once (each head pass is recomputed under its own
    checkpoint)."""
    import json
    import os

    from ps_tpu.models import joyai

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "joyai-llm-flash.json")) as f:
        cfg = joyai.JoyaiConfig.from_dict(json.load(f))

    on_chip = functools.partial(_described, one_chip)

    params = on_chip(jax.eval_shape(
        lambda: joyai.init_params(jax.random.key(0), cfg)))
    ids = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one_chip)
    bias = on_chip(jax.eval_shape(lambda: joyai.init_expert_bias(cfg)))
    loss = joyai.make_loss_fn(cfg, attn="flash")
    compiled = jax.jit(jax.value_and_grad(loss, has_aux=True)).lower(
        params, {"inputs": ids, "targets": ids}, bias).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    flash = [line for line in calls if "ps.attn/full" in line]
    assert len(flash) == 18
    assert all("ps.attn/full" in line or "ps.moe/expert" in line
               for line in calls)
    assert not [line for line in flash if "while" in line]
    text = compiled.as_text()
    assert text.count(" while(") == 2 * 5 and " conditional(" not in text
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 3.5e9
    assert (memory.argument_size_in_bytes + memory.output_size_in_bytes
            + memory.temp_size_in_bytes) < 0.6 * 17.18e9


def test_ouros_step_compiles_at_the_cells_shape(one_chip, no_compile_cache):
    """``ouro-2.6b.s8192.b1.zipf``: value and gradient of the loss
    ``KVStore.make_step`` differentiates, at the configuration's published
    widths and [1, 8192] tokens, the passes scanned. One copy of the stack in
    the program: 24 Mosaic flash calls, three for each of the eight layers,
    all of them inside the passes' two loops (forward and backward; each
    application's checkpoint keeps the forward call's output and logsumexp,
    so none runs twice), where the unrolled passes would write 96; and the
    rotation's 48 (``ops/rope.py``: q and k of a layer in the forward loop,
    again in the backward loop's recomputation, and the same pass on dq and
    dk), between which and the projection's ``bf16[1,16,8192,128]`` no f32
    array of q's or k's size, whole or in halves, is written (ISSUE 64). The
    program's arguments are the parameters once and its results their
    gradients once (2.45e9 B each: one f32 gradient a weight, the four
    cotangents summed in the backward loop's carry); the temporaries (the
    kept activations of 32 applications, the loops' bf16 copies of the
    weights, the gradients' accumulators) stay under 5.4e9 B (5.17e9 compiled; 5.60e9 while
    each pass ran its own readout inside the loop), and no [8192, 49152]
    array of logits is among them."""
    import json
    import os

    from ps_tpu.models import ouro

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "ouro-2.6b.json")) as f:
        cfg = ouro.OuroConfig.from_dict(json.load(f))

    on_chip = functools.partial(_described, one_chip)

    params = on_chip(jax.eval_shape(
        lambda: ouro.init_params(jax.random.key(0), cfg)))
    ids = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one_chip)
    loss = ouro.make_loss_fn(cfg, attn="flash")
    compiled = jax.jit(jax.value_and_grad(loss, has_aux=True)).lower(
        params, {"inputs": ids, "targets": ids}).compile()
    text = compiled.as_text()
    rotations, flash = _mosaic_calls(text)
    assert len(flash) == 3 * cfg.num_hidden_layers
    # q and k: forward, recomputation, and the cotangent's (the same pass)
    assert len(rotations) == 6 * cfg.num_hidden_layers
    assert sum("rope_transposed" in line for line in rotations) \
        == 2 * cfg.num_hidden_layers
    assert all("ps.loop" in line and "ps.attn" in line and "while" in line
               for line in rotations + flash)
    # the rotation writes the flash call's operand and nothing else: no f32
    # copy of a projection's product and no half-width f32 array
    assert not [kind for kind in _written(text)
                if "f32[1,8192,16,128]" in kind or "f32[1,8192,16,64]" in kind
                or "f32[1,16,8192,128]" in kind]
    assert "8192,49152]" not in text and "2048,49152]" in text
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes < 2.46e9
    assert memory.output_size_in_bytes < 2.46e9
    assert memory.temp_size_in_bytes < 5.4e9


#: [B, S, query heads, K/V heads] of 128 channels, the window or None: the
#: cells whose q and k reach ``blocks.rope`` behind an RMSNorm over each head
ROTATIONS = {"trinity-mini.s16384.b1.zipf, windowed": ((1, 16384, 32, 4), 2048),
             "sdar-30b-a3b.s8192.b1.zipf.bd4": ((2, 8192, 32, 4), None),
             "mellum2-12b-a2.5b.s8192.b1.zipf.x4, a chip's": (
                 (1, 8192, 32, 4), 1024)}


@pytest.mark.parametrize("cell", sorted(ROTATIONS))
def test_the_rotation_compiles_behind_a_head_norm_at_the_cells_shapes(
        cell, one_chip, no_compile_cache):
    """Projection, ``rms_norm`` over a head, ``blocks.rope``, the flash call
    and their gradient as Trinity's windowed layers, SDAR's and Mellum's
    write them: the rotation is ``ops/rope.py``'s Mosaic call (``path``) at
    tiles that fit the VMEM, twice forward and twice transposed beside the
    flash kernels' three; the norm's bf16 output is the call's operand as it
    stands (no ``copy`` in front of it, the transposition a bitcast), the
    call's output the flash call's; and no f32 array of q's or k's size in
    halves or head-major is written: what ``rope``'s ``jax.numpy`` form made
    XLA write three times a tensor (ISSUE 64)."""
    from ps_tpu.models import blocks
    from ps_tpu.ops import rope

    (b, s, h, h_kv), window = ROTATIONS[cell]
    d = 2048

    def loss(x, wq, wk, wv, sq, sk):
        def proj(w, n):
            return (x @ w).reshape(b, s, n, 128)

        q = blocks.rope(blocks.rms_norm(proj(wq, h), sq, 1e-6), 1e4)
        k = blocks.rope(blocks.rms_norm(proj(wk, h_kv), sk, 1e-6), 1e4)
        assert rope.path(q) == rope.path(k) == "kernel"
        return jnp.sum(flash_attention(q, k, proj(wv, h_kv), causal=True,
                                       window=window).astype(jnp.float32))

    def arg(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
        arg(b, s, d), arg(d, h * 128), arg(d, h_kv * 128),
        arg(d, h_kv * 128), arg(128, dtype=jnp.float32),
        arg(128, dtype=jnp.float32)).compile().as_text()
    rotations, flash = _mosaic_calls(text)
    assert len(rotations) == 4 and len(flash) == 3
    assert sum("rope_transposed" in line for line in rotations) == 2
    made = {line.split(" = ")[0].strip(): line for line in text.splitlines()
            if " = " in line}
    for line in rotations:
        operand = line.split("custom-call(")[1].split(",")[0]
        assert " copy(" not in made[operand], made[operand][:200]
    written = " ".join(_written(text))
    for n in (h, h_kv):
        assert f"f32[{b},{s},{n},64]" not in written
        assert f"f32[{b},{n},{s},128]" not in written
        # at a batch of two XLA hands the norm its projection in f32 (a
        # ``convolution_convert_fusion`` and a re-laid copy of it, before
        # this kernel as after): the norm's, not the rotation's
        assert b > 1 or f"f32[{b},{s},{n},128]" not in written


@pytest.mark.parametrize("dim,rule", [(32, "adagrad"), (1, "sgd")],
                         ids=["deep", "wide"])
def test_a_tables_distinct_pull_and_held_push_compile_at_the_cells_shape(
        dim, rule, one_chip, no_compile_cache, capsys):
    """``widedeep-criteo.b4096.zipf``: one table's ``plan_pull`` ->
    ``lookup_distinct`` -> a loss -> ``apply_held`` as the composite step
    runs them on one chip, at ``f32[33800000, dim]`` and 106,496 pairs. The
    donated table is written where it came in and in the layout it came in
    with, rows minor; no ``copy`` and no ``transpose`` makes a table-sized
    array (the dim-1 table's re-layout to a vector and back is the
    ``reduce`` and the loop PERF.md §5 counts, 1.9 ms a step, before this
    pull as after); and the gather of all N pairs stands in a
    ``conditional``'s branch alone. Prints the layout the compiler gave the
    batch-sized buffer of held rows (PERF.md §7 row 13)."""
    import ps_tpu as ps
    from ps_tpu.kv.sparse import SparseEmbedding

    rows, batch, features = 33_800_000, 4096, 26

    def on_chip(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    ps.init(backend="tpu", mesh_shape={"data": 1})
    try:
        emb = SparseEmbedding(rows, dim, optimizer=rule, learning_rate=0.05)
        assert emb.pulls_distinct
        table = on_chip((rows, dim))
        state = jax.tree_util.tree_map(
            lambda x: on_chip(x.shape, x.dtype),
            jax.eval_shape(emb._opt.init, table))

        def pull_and_push(table, state, ids, weight):
            plan = emb.plan_pull(ids)
            pulled, held = emb.lookup_distinct(table, ids, plan)
            loss, grads = jax.value_and_grad(lambda r: jnp.sum(
                jnp.tanh(r.astype(jnp.bfloat16).astype(jnp.float32))
                * weight))(pulled)
            return emb.apply_held(table, state, plan, held,
                                  grads.reshape(-1, dim)) + (loss,)

        compiled = jax.jit(pull_and_push, donate_argnums=(0, 1)).lower(
            table, state, on_chip((batch, features), jnp.int32),
            on_chip((batch, features, dim))).compile()
    finally:
        ps.shutdown()
    text = compiled.as_text()
    head = text.splitlines()[0]
    tiles = "T(8,128)" if dim > 1 else "T(1,128)"
    stored = f"f32[{rows},{dim}]{{0,1:{tiles}}}"
    assert f"entry_computation_layout={{({stored}," in head
    assert f")->({stored}," in head
    assert "{0}: (0, {}, may-alias)" in head
    table_sized = [ln for ln in text.splitlines() if re.search(
        rf"= \w+\[(1,1,)?{rows}[\],]\S* (copy|copy-start|transpose)\(", ln)]
    assert not table_sized, table_sized
    # every pair's row out of the table: only where an id lacks one
    n = batch * features
    whole = [ln for ln in text.splitlines()
             if " gather(" in ln and f"[{batch},{features}" in ln]
    assert whole and all("cond/branch" in ln for ln in whole), whole
    pull = next(ln for ln in text.splitlines()
                if " while(" in ln and 'ps.lookup/while"' in ln)
    held = re.findall(rf"f32\[{n},{dim}\]\{{[^}}]*\}}" if dim > 1
                      else rf"f32\[{n}\]\{{[^}}]*\}}",
                      pull[:pull.index(" while(")])
    assert len(held) == 1, pull
    with capsys.disabled():
        print(f"\nheld rows of the dim-{dim} table, as the v5e compiler "
              f"lays them out in the pull's loop: {held[0]}")


# -- Phi-4-mini-flash: the selective scan and the step -------------------------

def _no_state_a_token(text: str, seq: int, channels: int, state: int):
    """No array a compiled program writes has a token axis of ``seq`` (whole
    or as chunks x tokens) beside both the channels and the states: what
    Mamba-1's scan would keep if it kept a state a token."""
    full = seq * channels * state
    for kind in _written(text):
        for dims in re.findall(r"\[([0-9,]+)\]", kind):
            size = 1
            for n in dims.split(","):
                size *= int(n)
            assert size < full, kind


def _selective_gradient(one_chip, channels, state, seq=16384):
    """The gradient of ``ops/selective_scan.py::selective_scan`` in all six
    operands, compiled for the described chip at ``x`` bf16[1, seq,
    channels] on ``state`` states."""
    from ps_tpu.ops.selective_scan import selective_scan

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (arg((1, seq, channels), jnp.bfloat16),
            arg((1, seq, channels), jnp.float32),
            arg((channels, state), jnp.float32),
            arg((1, seq, state), jnp.bfloat16),
            arg((1, seq, state), jnp.bfloat16), arg((channels,), jnp.float32),
            arg((1, seq, channels), jnp.float32))

    def gradient(x, dt, a, b, c, d, w):
        return jax.grad(lambda *o: jnp.sum(w * selective_scan(*o)),
                        argnums=range(6))(x, dt, a, b, c, d)

    return jax.jit(gradient).lower(*args).compile()


def test_the_selective_scan_compiles_at_the_phi4flash_cells_shape(
        one_chip, no_compile_cache):
    """``ops/selective_scan.py`` at ``phi-4-mini-flash-reasoning.s16384.b1.
    zipf``'s mixer, 5,120 channels on a state of 16 over 16,384 tokens,
    forward and backward: the two Mosaic calls of ``ops/
    selective_scan_mosaic.py`` (``path`` says ``"kernel"``), no ``while``
    over tokens outside them, and no array of [16384, 5120, 16] f32 entries
    (5.4e9 B) or anything near it: what lives between the calls is the state
    that entered each tile, and the temporaries (that, ``B`` and ``C`` with
    the token last, ``dA`` a tile before its sum) stay under 0.6e9 B, where
    the arguments are 0.84e9."""
    from ps_tpu.ops.selective_scan import path
    from ps_tpu.ops.selective_scan_mosaic import TILE

    seq, channels, state = 16384, 5120, 16
    assert path(jax.ShapeDtypeStruct((1, seq, channels), jnp.bfloat16),
                jax.ShapeDtypeStruct((channels, state), jnp.float32)) \
        == "kernel"
    compiled = _selective_gradient(one_chip, channels, state)
    text = compiled.as_text()
    _, calls = _mosaic_calls(text)
    assert ["s6_forward" in line for line in calls] == [True, False]
    assert "s6_backward" in calls[1]
    assert " while(" not in text
    _no_state_a_token(text, seq, channels, state)
    # one state a tile between the calls (21 MB: the compiler may hold it in
    # the chip's fast memory, ``S(1)``, where it is no temporary of the HBM's)
    assert f"f32[{seq // TILE},1,{state},{channels}]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9


def test_the_selective_scans_xla_form_compiles_where_the_kernels_do_not(
        one_chip, no_compile_cache):
    """The same shape on 12 states, which are no whole registers of eight
    sublanes: ``path`` says ``"xla"`` and the compiled gradient is what PR
    65's was,
    two ``while`` loops over the chunks (each with the unrolled tokens' loop
    inside), no Mosaic call, the states that entered each chunk and one
    chunk's products kept and no state a token."""
    from ps_tpu.ops.selective_scan import CHUNK, path

    seq, channels, state = 16384, 5120, 12
    assert path(jax.ShapeDtypeStruct((1, seq, channels), jnp.bfloat16),
                jax.ShapeDtypeStruct((channels, state), jnp.float32)) \
        == "xla"
    compiled = _selective_gradient(one_chip, channels, state)
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' not in text
    assert " while(" in text
    _no_state_a_token(text, seq, channels, state)
    entering = seq // CHUNK * state * channels * 4
    assert entering <= compiled.memory_analysis().temp_size_in_bytes < 0.6e9


def test_phi4flashs_step_compiles_at_the_cells_shape(one_chip,
                                                     no_compile_cache):
    """``phi-4-mini-flash-reasoning.s16384.b1.zipf``: value and gradient of
    the loss ``KVStore.make_step`` differentiates, at the configuration's
    published widths and [1, 16384] tokens. Nine Mosaic flash calls, three
    for each differential layer (each layer's checkpoint keeps the forward
    call's output and logsumexp, so none runs twice), the window layer's the
    band's; six calls of the selective scan's kernels under ``ps.mamba/s6``,
    three for each Mamba-1 layer (forward, forward again in the layer's
    recomputation, backward); no array with a state a token; no
    [16384, 25008] array of logits.
    The program's arguments are the parameters once and its results their
    gradients once (2.79e9 B each); the temporaries stay under 4.5e9 B, which
    with the store's two moments (5.58e9) leaves the chip's 17.18e9 a margin
    the run's peak confirms (``PERF.md`` section 5)."""
    import json
    import os

    from ps_tpu.models import phi4flash

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "phi-4-mini-flash-reasoning.json")) as f:
        cfg = phi4flash.Phi4FlashConfig.from_dict(json.load(f))

    on_chip = functools.partial(_described, one_chip)

    params = on_chip(jax.eval_shape(
        lambda: phi4flash.init_params(jax.random.key(0), cfg)))
    ids = jax.ShapeDtypeStruct((1, 16384), jnp.int32, sharding=one_chip)
    loss = phi4flash.make_loss_fn(cfg, attn="flash")
    compiled = jax.jit(jax.value_and_grad(loss)).lower(
        params, {"inputs": ids, "targets": ids}).compile()
    text = compiled.as_text()
    _, calls = _mosaic_calls(text)
    scan = [line for line in calls if "ps.mamba/s6" in line]
    assert len(scan) == 6
    assert sum("s6_backward" in line for line in scan) == 2
    flash = [line for line in calls if line not in scan]
    assert len(flash) == 9
    assert sum("ps.attn/window" in line for line in flash) == 3
    assert sum("ps.attn/cross" in line for line in flash) == 3
    assert sum("ps.attn/full" in line for line in flash) == 3
    _no_state_a_token(text, 16384, 5120, 16)
    assert "16384,25008]" not in text and "2048,25008]" in text
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes < 2.80e9
    assert memory.output_size_in_bytes < 2.80e9
    assert memory.temp_size_in_bytes < 4.5e9
