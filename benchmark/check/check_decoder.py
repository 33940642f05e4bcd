#!/usr/bin/env python3
"""Checks of the decoder reader and of the flash kernel's one count, on the
CPU, with no chip and no step:

    JAX_PLATFORMS=cpu python3 benchmark/check/check_decoder.py

``families/flash.py`` against counts made by hand and against the numbers
``tests/`` hold; ``layer_metrics/decoder.py``, ``kernel.py`` and ``step.py``
on a hand-made result of a two-chip decoder whose grouped matmuls and
rotations are Mosaic calls that carry their scope, with the finer marks and
the selective scan; the marks against the program's; ``BENCHMARK.json``'s
names; the backward form BERT's count takes against the op's rule. With
``--rehearse [cell ...]`` (every cell without a name; a process of 15-40 s
each) also the rule of the lists, as far as ``tests/`` leave it room: a name
listed for a cell is one its rehearsal gives, and the four ``host.*`` names,
``step.mfu`` and every listed ``decoder.*`` and ``kernel.*`` name take each
cell whose rehearsal gives them (``check_lists`` says where not, and why).
"""

from __future__ import annotations

import importlib
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.families import flash  # noqa: E402
from benchmark.layer_metrics import (decoder, kernel, ouro, scope,  # noqa: E402
                                     step, twin)

#: the six cells whose reader PR 49 merged, by the prefix their names keep
DECODER_CELLS = {
    "moe": "olmoe-1b-7b.s4096.zipf", "lfm2": "lfm2-24b-a2b.s8192.zipf",
    "kimi": "kimi-linear-48b-a3b.s8192.b1.zipf",
    "nemo": "nemotron-3-super-120b-a12b.s8192.b1.zipf",
    "trinity": "trinity-mini.s16384.b1.zipf",
    "mellum": "mellum2-12b-a2.5b.s8192.b1.zipf.x4"}
#: the names whose ``workloads`` follow the rehearsals, no more and no fewer
#: (``check_lists``), beside every listed ``decoder.*`` and ``kernel.*`` name
RULED = ("host.step_launch_ms", "host.step_wrap_ms", "host.input_place_ms",
         "host.input_mb_per_step", "step.mfu")
#: the cells whose lists ``tests/`` hold as they were, with the names they
#: hold them in (``tests/test_joyai.py`` the cell in none;
#: ``test_granite_h.py``, ``test_ouro.py``, ``test_phi4flash.py`` theirs at
#: ``step.mfu`` and ``kernel.flash_roofline``)
_TWO = ("step.mfu", "kernel.flash_roofline")
HELD = {"joyai-llm-flash.s8192.b1.zipf": (),
        "granite-4.0-h-micro.s8192.b1.zipf": _TWO,
        "ouro-2.6b.s8192.b1.zipf": _TWO,
        "phi-4-mini-flash-reasoning.s16384.b1.zipf": _TWO}


def close(a, b, rel=1e-12):
    return abs(a - b) <= rel * max(abs(a), abs(b))


def check_flash_cost():
    # BERT at 512: no mask over positions, 32 x 12 heads of 64, 12 layers,
    # the forward's two matmuls and the one backward call's five
    flops, nbytes = flash.cost(32, 12, 12, 512, 64, 64, 12,
                               flash.seen_pairs(512, causal=False),
                               "one call")
    assert flash.seen_pairs(512, causal=False) == 512 * 512
    assert flops == 12 * 32 * 12 * 7 * (2 * 512 * 512 * 64)
    assert nbytes == 12 * 32 * 12 * 512 * (
        (4 * 64 * 2 + 4)            # q, k, v read, o written, the logsumexp
        + (7 * 64 * 2 + 4))         # q, dO, k, v read, dk, dv, dq written
    # 3.5 times what the forward alone counted, as ISSUE 49 reckons
    forward, _ = flash.cost(32, 12, 12, 512, 64, 64, 12, 512 * 512, None)
    assert flops == 3.5 * forward
    # OLMoE at 4,096: the triangle with its diagonal, 2 x 16 heads of 128,
    # one layer, two matmuls forward, four in dk / dv, three in dq
    pairs = flash.seen_pairs(4096)
    assert pairs == 4096 * 4097 // 2
    flops, nbytes = flash.cost(2, 16, 16, 4096, 128, 128, 1, pairs)
    assert flops == 2 * 16 * 9 * (2 * pairs * 128)
    assert nbytes == 2 * 16 * 4096 * (
        (4 * 256 + 4)               # forward
        + (6 * 256 + 8)             # dk / dv: q, dO, k, v, dk, dv, two rows
        + (5 * 256 + 8))            # dq: q, dO, k, v, dq, two rows
    # what the families' own functions gave before this file, to the digit
    # (tests/test_kimi_linear.py, test_nemotron_h.py, test_trinity.py,
    # test_lfm2.py hold the same numbers): the latent attention's 32 heads
    # with keys of 192 and values of 128 over half the square, as the Kimi
    # cell counted them until PR 67 and JoyAI's does
    flops, nbytes = flash.cost(1, 32, 32, 8192, 192, 128, 1, 8192 * 8192 / 2)
    assert flops == 32 * 8192 * 8192 * (5 * 192 + 4 * 128)
    assert nbytes == 32 * 8192 * (
        (2 * 192 * 2 + 2 * 128 * 2 + 4)
        + (2 * 192 * 2 + 2 * 128 * 2 + 8 + (192 + 128) * 2)
        + (2 * 192 * 2 + 2 * 128 * 2 + 8 + 192 * 2))
    # with the diagonal, as the Kimi, Nemotron-H and Granite cells count
    # since PR 67: 1 / 8,192 more
    assert flash.cost(1, 4, 4, 8192, 128, 128, 1, flash.seen_pairs(8192))[0] \
        == 4 * 8192 * 8193 * 9 * 128
    band, band_bytes = flash.cost(1, 32, 4, 16384, 128, 128, 1,
                                  flash.seen_pairs(16384, 2048))
    triangle, _ = flash.cost(1, 32, 4, 16384, 128, 128, 1,
                             flash.seen_pairs(16384))
    assert band == 31_458_304 * 32 * 2304
    assert triangle == 134_225_920 * 32 * 2304
    q_side, k_side = 128 * 2 * 32, 128 * 2 * 4
    assert band_bytes == 16384 * (
        (2 * q_side + 2 * k_side + 4 * 32) + (2 * q_side + 4 * k_side + 256)
        + (3 * q_side + 2 * k_side + 256))
    # the forward calls alone, what LFM2's roofline counted before PR 33
    assert flash.cost(2, 32, 8, 8192, 64, 64, 1, 8192 * 8192 / 2, None) == (
        2 * 2 * 32 * 8192 * 8192 * 64,
        2 * 2 * 40 * 8192 * 64 * 2 + 4 * 2 * 32 * 8192)
    # BERT's backward form follows the op's own rule: one call where a tile
    # spans the sequence, two past it (dense_step.build asks the same)
    from ps_tpu.ops.flash_attention import backward_tiles
    assert backward_tiles(512, 64, 2, False) == (512, 512)
    assert backward_tiles(1024, 64, 2, False) != (1024, 1024)
    print("flash.cost: BERT's full mask at 512 and OLMoE's causal one at "
          "4,096 by hand, the families' older numbers to the digit: ok")


def check_marks():
    """The reader's copies against the program's names, where the program is
    there to ask: ``tests/test_phases.py`` holds ``decoder.SCOPES``; the
    finer marks, the scan's and the rotation's kernel names are held here."""
    try:
        from ps_tpu.obs import phases
        from ps_tpu.ops import rope
    except ImportError:
        print("marks: no program beside this benchmark, nothing to hold")
        return
    assert set(decoder.MARKS) == {
        phases.ATTN_INBLOCK, phases.ATTN_LATENT, phases.ATTN_ROPE,
        phases.MAMBA_GATE, phases.MTP, phases.MTP_JOIN}
    assert not set(decoder.MARKS) & set(decoder.METRICS)
    assert kernel.S6 == phases.MAMBA_S6
    assert set(ouro.MARKS) == {phases.LOOP, phases.EXIT}
    with open(rope.__file__) as f:
        text = f.read()
    assert 'name="rope_transposed" if transposed else "rope"' in text
    assert decoder.ROTATION == "%rope"
    print(f"marks: {len(decoder.MARKS)} finer marks, the scan's and the "
          "rotation's kernel names equal the program's: ok")


def _ev(own, opcode="fusion", shape="f32[8]", tail=""):
    return (f"{own} = {shape} {opcode}(%p0), kind=kLoop, "
            "calls=%fused_computation, " + "backend_config={} " * 8 + tail)


def check_reader():
    call = 'custom_call_target="tpu_custom_call"'
    cp = "jit(f)/ps.grad/jvp()/checkpoint/"
    exchange = cp + "ps.moe/combine/ps.moe/exchange/all_to_all"
    rows = "bf16[4,96,8]"
    ops = {_ev("%qkv"): 0.004, _ev("%pack"): 0.001,
           _ev("%band", "custom-call", tail=call): 0.008,
           _ev("%triangle", "custom-call", tail=call): 0.010,
           # the rotation's two Mosaic calls under ps.attn: no flash call
           _ev("%rope.3", "custom-call", tail=call): 0.001,
           _ev("%rope_transposed.4", "custom-call", tail=call): 0.001,
           # the own blocks inside ps.attn, the selective scan inside
           # ps.mamba: read by their marks, and in the outer scope's metric
           _ev("%own"): 0.002, _ev("%s6", "custom-call", tail=call): 0.004,
           _ev("%gate"): 0.002, _ev("%route"): 0.001, _ev("%rows"): 0.003,
           _ev("%back"): 0.001,
           _ev("%all-to-all.1", "all-to-all", rows): 0.006,
           _ev("%all-to-all.2", "all-to-all", rows): 0.004,
           _ev("%all-gather.1", "all-gather"): 0.003,
           # a Mosaic grouped matmul: a custom call to the kernels' target
           # that keeps the scope of where it is called
           _ev("%gmm.1", "custom-call", tail=call): 0.008,
           _ev("%ce"): 0.005, _ev("%embed"): 0.001, _ev("%adam"): 0.007}
    names = {"%qkv": "jit(f)/ps.grad/transpose(jvp())/ps.attn/dot_general",
             "%pack": cp + "ps.attn/ps.attn/window/transpose",
             "%band": cp + "ps.attn/ps.attn/window/pallas_call",
             "%triangle": "jit(f)/ps.grad/transpose(ps.grad)/checkpoint/"
                          "ps.attn/ps.attn/full/pallas_call",
             "%rope.3": cp + "ps.attn/pallas_call",
             "%rope_transposed.4": cp + "ps.attn/ps.attn/full/pallas_call",
             "%own": cp + "ps.attn/ps.attn/inblock/reduce",
             "%s6": cp + "ps.mamba/ps.mamba/s6/pallas_call",
             "%gate": cp + "ps.attn/ps.attn/gate/mul",
             "%route": cp + "ps.moe/route/dot",
             "%rows": cp + "ps.moe/dispatch/gather",
             "%back": "jit(f)/ps.grad/transpose(jvp())/ps.moe/combine/gather",
             "%all-to-all.1": cp + "ps.moe/dispatch/ps.moe/exchange/"
                                   "all_to_all",
             "%all-to-all.2": exchange,
             "%all-gather.1": "jit(f)/ps.apply/sharding_constraint",
             "%gmm.1": cp + "ps.moe/expert/pallas_call",
             "%ce": "jit(f)/ps.grad/jvp(ps.head)/reduce",
             "%embed": "jit(f)/ps.grad/jvp()/gather",
             "%adam": "jit(f)/ps.apply/mul"}
    # two chips, the second with an exchange twice as long: the collectives
    # are the worst chip's, everything else the mean
    slow = {k: v * (2 if "all-to-all" in k else 1) for k, v in ops.items()}
    r = {"trace": {"devices": {"d0": {"ops": ops}, "d1": {"ops": slow}},
                   "busy_s": 0.072},
         "traced_steps": 2, "chips": 2,
         "counters": {"exchange_rows_per_step": 1e6, "dropped_tokens": 0.0,
                      "live_pairs_per_step": 1000.0, "held_pair_share": 0.5,
                      "load_max_over_mean": 3.0, "masked_share": 0.5},
         "facts": {"kernel_targets": ["tpu_custom_call"],
                   "dense_flops_per_step": 4e9, "flops_per_pair": 1e6,
                   "exchange_bytes_per_row": 150.0,
                   "exchange_buffer_rows": 96, "layers": 1,
                   "window_flash_flops": 1e9, "window_flash_bytes": 1.0,
                   "flash_flops": 1.0, "flash_bytes": 2e9,
                   "scan_flops": 1e9, "scan_bytes": 1.0},
         "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e12,
                   "ici_bits_per_s": 8e11, "vector_ops_per_s": 1e12},
         "steps": 10, "window_s": 1.0}
    out = decoder.scope_times(r, names)
    want = {"decoder.attn_ms": 14.5,          # cores, gate, rotation, own in
            "decoder.window_core_ms": 4.5,
            "decoder.full_core_ms": 5.5,      # a rotation under it
            "decoder.inblock_ms": 1.0,        # by its mark
            "decoder.mamba_ms": 2.0,          # the scan, in no finer scope

            "decoder.attn_gate_ms": 1.0, "decoder.route_ms": 0.5,
            "decoder.dispatch_ms": 2.0,       # with combine, less exchange
            "decoder.expert_ms": 4.0, "decoder.head_ms": 2.5,
            "decoder.exchange_ms": 10.0,      # the slower chip's
            "decoder.exchange_exposed_ms": 10.0,
            "decoder.store_collective_ms": 1.5,
            # 1e6 rows x 150 B x 2 exchanges over 1e11 B/s: 3 ms of the 10
            "decoder.exchange_ici_share": 30.0,
            "decoder.expert_mxu_share": 25.0,   # 1 of 4 ms
            # each kind over its own calls; the grouped matmul's Mosaic call
            # is under ps.moe/expert and in neither, and the rotation's two
            # under ps.attn are no flash calls: 2 of the triangle's 5 ms
            "kernel.window_flash_roofline": 25.0,
            "kernel.flash_roofline": 40.0}
    assert set(out) == set(want), sorted(set(out) ^ set(want))
    for k, v in want.items():
        assert close(out[k], v, 1e-9), (k, out[k], v)
    scope.loaded_op_names = lambda: names
    whole = decoder.read(r)
    assert whole["decoder.held_pair_share"] == 0.5
    assert whole["decoder.masked_share"] == 0.5
    # no family states the windowed call's live steps any more
    assert set(whole) == set(want) | set(decoder.COUNTS.values()) - {
        "decoder.window_live_step_share"}
    # kernel.py takes the rooflines from the result kept in r, and reads the
    # scan by its mark: 2 ms a step, 1 ms of vector operations
    assert kernel.read(r) == {
        **{k: whole[k] for k in want if k.startswith("kernel.")},
        "kernel.s6_ms": 2.0, "kernel.s6_roofline": 50.0}
    assert "kernel.s6_roofline" not in kernel.read(
        {**r, "peaks": {k: v for k, v in r["peaks"].items()
                        if k != "vector_ops_per_s"}})
    # one walk a run: without the scan's cost nothing asks for the marks again
    scope.loaded_op_names = None
    plain = {**r, "facts": {k: v for k, v in r["facts"].items()
                            if not k.startswith("scan_")}}
    assert kernel.read(plain) == {k: whole[k] for k in want
                                  if k.startswith("kernel.")}
    scope.loaded_op_names = lambda: names
    # a rehearsal lists the same names and no value
    listed = decoder.read({k: v for k, v in r.items()
                           if k not in ("decoder", "trace")} | {"peaks": {}})
    assert set(listed) == set(whole) and not any(
        v for k, v in listed.items() if k not in decoder.COUNTS.values())
    assert kernel.selective_scan({**r, "peaks": {}}) == {
        "kernel.s6_ms": 0.0, "kernel.s6_roofline": 0.0}
    # two chips' FLOPs over two chips' peak: a chip's over one's
    got = step.read(r)
    assert close(got["step.mfu"], 5.0, 1e-9), got   # 5e9 x 10 / s of 1e12
    assert close(got["step.device_ms"], 36.0, 1e-9), got
    # a dense step states the whole step's, and nothing of a decoder's
    dense = {k: v for k, v in r.items() if k != "decoder"}
    dense.update(facts={"flops_per_step": 1e10}, counters={})
    assert close(step.read(dense)["step.mfu"], 5.0, 1e-9)
    assert decoder.read(dense) == {} and kernel.read(dense) == {}
    # a program without the scopes: nothing to read, nothing at 0
    assert decoder.scope_times(r, {}) == {}
    assert decoder.read({"counters": {}, "facts": {}}) == {}
    print("decoder.py, kernel.py, step.py on a hand-made two-chip result: "
          f"{len(whole)} names: ok")


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def check_manifest():
    manifest = _manifest()
    cells = {w["name"] for w in manifest["workloads"]}
    configs = {c["name"] for c in manifest["configs"]}
    listed = {m["name"]: m for m in manifest["per_layer"]}
    assert len(listed) == len(manifest["per_layer"]) <= 128
    for m in manifest["per_layer"]:
        assert set(m.get("workloads", ())) <= cells, m["name"]
        assert not any(m["name"].startswith(c) for c in configs | cells), \
            m["name"]
    # each older reader lists exactly the names its module maps to, in its
    # cell alone, and each is the older name of one of the one reader's
    known = set(decoder.METRICS.values()) | set(decoder.ROOFLINES) | set(
        decoder.COUNTS.values()) | {
        "decoder.expert_mxu_share", "decoder.conv_gate_hbm_share",
        "decoder.exchange_exposed_ms", "decoder.exchange_ici_share",
        "decoder.store_collective_ms", "step.mfu"}
    older = 0
    for prefix, cell in DECODER_CELLS.items():
        module = importlib.import_module(f"benchmark.layer_metrics.{prefix}")
        assert set(module.LISTED) <= known, set(module.LISTED) - known
        theirs = {n for n in listed if n.startswith(prefix + ".")}
        assert theirs == set(twin.names_of(
            prefix, module.LISTED, module.RENAMED).values()), prefix
        assert all(listed[n]["workloads"] == [cell] for n in theirs), prefix
        older += len(theirs)
        # and the cell has no reading under two names
        twice = {n for n in module.LISTED
                 if cell in listed.get(n, {}).get("workloads", ())}
        assert not twice, (cell, twice)
    # 87 until PR 67: the six <p>.mfu are step.mfu, the six flash rooflines
    # kernel.flash_roofline, and the constant of PR 53's lost grid is gone
    assert older == 74 and "trinity.window_live_step_share" not in listed
    # a name of the one reader's own that is listed is one it can give
    own = {n for n in listed if n.startswith(("decoder.", "ouro."))}
    assert own <= known | set(decoder.MARKS.values()) | set(
        ouro.MARKS.values()) | set(ouro.COUNTS.values()), own
    print(f"BENCHMARK.json: {len(listed)} per-layer metrics, {older} of them "
          f"the six older readers' names, {len(own)} the one reader's own: ok")


def rehearse(cell: str) -> dict:
    """The result line of ``run.py --rehearse --trace 1`` of one cell."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS",
                        "JAX_COMPILATION_CACHE_DIR")}
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--rehearse", "--trace", "1", "--seconds", "1"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def check_lists(cells):
    """The rule of the lists where the tests leave it room. Listed, so
    rehearsed: every cell (``tests/test_phases.py`` holds it for the cells it
    rehearses). Rehearsed, so listed: a cell is in the ``workloads`` of a
    ``RULED`` name or of a listed ``decoder.*`` / ``kernel.*`` name exactly
    where its rehearsal gives the name, the tests do not hold the cell out
    of the list (``HELD``) and its older reader
    has no name of its own for the reading (the view's ``LISTED``); the
    dense cells' ``step.mfu`` lists are a copied constant's and stay as they
    were. What else a cell's readers give (the one reader's names that the
    manifest has no slot for, the finer marks, Ouro's and the scan's) is
    printed: the swap of PERF.md section 7, row 0."""
    manifest = _manifest()
    views = {cell: set(importlib.import_module(
        f"benchmark.layer_metrics.{prefix}").LISTED)
        for prefix, cell in DECODER_CELLS.items()}
    dense = {w["name"] for w in manifest["workloads"]
             if w["config"] in ("resnet50", "bert-base")}
    ruled = [m for m in manifest["per_layer"] if "workloads" in m and (
        m["name"] in RULED or m["name"].startswith(("decoder.", "kernel.")))]
    for cell in cells or [w["name"] for w in manifest["workloads"]]:
        line = rehearse(cell)
        assert line["correct"], cell
        given = set(line["rehearsed"]) | set(line["unlisted"])
        # scope.py, sparse.py and collective.py read a device's trace and
        # nothing else: they list nothing without one
        mine = {m["name"] for m in manifest["per_layer"]
                if cell in m.get("workloads", ()) and not (
                    m["source"] == "device_trace" and m["name"].split(".")[0]
                    in ("scope", "sparse", "collective"))}
        # a dense cell's step.mfu is a constant copied for the cell's own
        # sizes, which the rehearsal's tiny ones lack, and BERT's roofline is
        # read from a trace's events alone: no rehearsal lists either
        apart = {"step.mfu", "kernel.flash_roofline"} if cell in dense \
            else set()
        lacking = mine - given - apart
        assert not lacking, (cell, sorted(lacking))
        for m in ruled:
            name = m["name"]
            if name in apart:
                continue
            held = cell in HELD and name not in HELD[cell]
            want = (name in given and not held
                    and name not in views.get(cell, ()))
            assert (cell in m["workloads"]) == want, (cell, name, want)
        # every view answers for any decoder: another cell's names are no
        # reading this cell lacks
        rest = [n for n in line["unlisted"]
                if n.split(".")[0] not in DECODER_CELLS]
        print(f"{cell}: {len(mine)} names listed for it by the rule; "
              f"unlisted {rest}", flush=True)


if __name__ == "__main__":
    check_flash_cost()
    check_marks()
    check_reader()
    check_manifest()
    if "--rehearse" in sys.argv[1:]:
        check_lists([a for a in sys.argv[1:] if a != "--rehearse"])
