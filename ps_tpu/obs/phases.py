"""Names of the phases of a fused step, of the host spans around it and of
the spans of a process's set-up.

Constants only. The device phases are opened with ``jax.named_scope`` where
the work is written (``kv/fused.py``, ``kv/sparse.py``,
``ops/sparse_apply.py``, ``models/olmoe.py``, ``models/lfm2.py``,
``models/kimi_linear.py``, ``models/nemotron_h.py``, ``models/trinity.py``,
``models/mellum.py``, ``models/sdar.py``, ``models/joyai.py``,
``models/granite_h.py``, ``models/qwen3_next.py``, ``models/ouro.py``,
``models/phi4flash.py``, ``models/blocks.py``,
``ops/moe.py``) and land in the
``op_name`` of every HLO instruction traced under them; the host spans are
recorded with ``ps_tpu.obs.tracer().program_span`` (``kv/fused.py``,
``data/prefetch.py``), the set-up spans too (``ps_tpu/__init__.py``,
``api.py``, ``kv/store.py``, ``kv/sparse.py``), and the compiler's spans by
the one listener on ``jax.monitoring`` (``obs/compiles.py``); what a
``step.launch`` says of the step's pace is ``obs/pace.py``'s.
``benchmark/layer_metrics/scope.py``, ``host.py`` and ``setup.py`` keep
their own copy of the names they look up (the benchmark also runs on trees
that lack this file); ``tests/test_phases.py`` holds them equal. Every span
here has a reader there, ``SETUP_SPANS`` and ``COMPILE_SPANS`` too
(``setup.*``): a span that no metric reads is not recorded.

One scope around ``jax.value_and_grad`` gives two phases: JAX writes the
forward ops as ``ps.grad/jvp(...)`` and the backward ops as
``ps.grad/transpose(jvp(...))`` in the same ``op_name``, or, for the ops of a
``jax.custom_vjp`` backward rule (the flash attention's), as
``ps.grad/transpose(ps.grad)/jvp(...)``.
"""

# -- device phases (jax.named_scope) -----------------------------------------
GRAD = "ps.grad"                # loss forward and backward
APPLY = "ps.apply"              # dense server-side apply: scale, opt.update, apply_updates
LOOKUP = "ps.lookup"            # sparse pull: table[ids]
ROW_APPLY = "ps.row_apply"      # sparse push, with the children below
ROW_EXCHANGE = "ps.row_apply/exchange"  # all_gather / all_to_all of ids and row grads
ROW_DEDUPE = "ps.row_apply/dedupe"      # sort and segment-sum at batch size
ROW_GATHER = "ps.row_apply/gather"      # take of the touched rows and their state
ROW_UPDATE = "ps.row_apply/update"      # the row-wise optimizer rule
ROW_SCATTER = "ps.row_apply/scatter"    # .at[dst].set of table and state

#: what tells a backward op from a forward op inside ``GRAD``: a transform
#: of the name stack (the primitive ``transpose`` has no parenthesis)
BACKWARD_MARK = "transpose("

DEVICE_PHASES = (GRAD, APPLY, LOOKUP, ROW_APPLY, ROW_EXCHANGE, ROW_DEDUPE,
                 ROW_GATHER, ROW_UPDATE, ROW_SCATTER)

# -- scopes inside the loss of the expert model (models/olmoe.py) --------------
# They nest under GRAD, so the phases above keep adding up; they are read by
# ``benchmark/layer_metrics/decoder.py``, which keeps its own copy. Never part
# of DEVICE_PHASES: that tuple is what ``layer_metrics/scope.py`` knows.
MOE_ROUTE = "ps.moe/route"        # router matmul, softmax, top-k, sort, group sizes
MOE_DISPATCH = "ps.moe/dispatch"  # token rows permuted into expert order
MOE_EXPERT = "ps.moe/expert"      # the three grouped matmuls and SwiGLU
MOE_COMBINE = "ps.moe/combine"    # rows permuted back, weighted sum over top-k
ATTN = "ps.attn"                  # q/k/v projections, QK-norm, RoPE, attention, out projection
HEAD = "ps.head"                  # final norm, head matmul, cross entropy

MOE_SCOPES = (MOE_ROUTE, MOE_DISPATCH, MOE_EXPERT, MOE_COMBINE, ATTN, HEAD)

# -- scopes of the hybrid decoder (models/lfm2.py), beside the six above -------
# Read by ``benchmark/layer_metrics/decoder.py``, which keeps its own copy.
# CONV_GATE nests under CONV, so CONV's time holds it.
CONV = "ps.conv"                  # the conv mixer: in projection, gates and taps, out projection
CONV_GATE = "ps.conv/gate"        # ops/gated_conv.py alone: the two gates and the causal taps
FFN = "ps.ffn"                    # the dense SwiGLU of the leading layers

LFM2_SCOPES = MOE_SCOPES + (CONV, CONV_GATE, FFN)

# -- scopes of Kimi-Linear (models/kimi_linear.py), beside the six and FFN ------
# Read by ``benchmark/layer_metrics/decoder.py``, which keeps its own copy. MLA
# is under ATTN, the dense SwiGLU under FFN. KDA_CONV and KDA_CORE nest under
# KDA, so KDA's time holds them.
KDA = "ps.kda"                    # the delta-rule mixer (Kimi-Linear's KDA, Qwen3-Next's Gated DeltaNet): projections, taps, gates, the rule, norm, out projection
KDA_CONV = "ps.kda/conv"          # the mixer's depthwise causal taps and their SiLU: Kimi's three filters, Qwen3-Next's one over q | k | v
KDA_CORE = "ps.kda/core"          # ops/kda.py alone: the chunked gated delta rule, with the broadcasts in front of it where the decay is a head's
MOE_SHARED = "ps.moe/shared"      # the shared expert, a SwiGLU every token passes

# ``models/blocks.py::mla_block``, the latent layer of this model and of
# JoyAI-LLM-Flash, opens three scopes under ATTN (so ATTN's time holds them):
# ATTN_FULL around the kernel call (Trinity's, Mellum's and SDAR's layers that
# see every earlier key open it too), and two that have no metric of their own
# yet and are read inside ``decoder.attn_ms``.
ATTN_FULL = "ps.attn/full"        # the core of a layer that sees every earlier key
ATTN_LATENT = "ps.attn/latent"    # latent attention's projections (q or q_a and q_b, kv_a, kv_b) and the latent norms
ATTN_ROPE = "ps.attn/rope"        # the rotations, where the model has positions, and the making of the 192-wide q and k

KIMI_SCOPES = MOE_SCOPES + (FFN, KDA, KDA_CONV, KDA_CORE, MOE_SHARED,
                            ATTN_FULL, ATTN_LATENT, ATTN_ROPE)

# -- scopes of Nemotron-H (models/nemotron_h.py), beside the six ----------------
# Read by ``benchmark/layer_metrics/decoder.py``, which keeps its own copy. The
# attention layer is under ATTN, the shared expert under MOE_SHARED.
# MAMBA_CONV, MAMBA_SSD and MAMBA_GATE nest under MAMBA, so MAMBA's time holds
# them; the three are opened by ``models/blocks.py::mamba_block``, the one
# mixer of this model and of Granite-4.0-H. MAMBA_GATE has no metric of its
# own yet and is read inside ``decoder.mamba_ms``.
MAMBA = "ps.mamba"                # the Mamba-2 mixer: in projection, filter, scan, gated norm, out projection
MAMBA_CONV = "ps.mamba/conv"      # the depthwise causal filter over x, B and C, its bias and SiLU
MAMBA_SSD = "ps.mamba/ssd"        # ops/ssd.py alone: the chunked scalar-decay scan
MAMBA_GATE = "ps.mamba/gate"      # the D skip, silu(z), their product and the gated norm: f32 elementwise over the mixer's channels
MOE_LATENT = "ps.moe/latent"      # the two projections between the model's width and the experts' latent

NEMOTRON_SCOPES = MOE_SCOPES + (MAMBA, MAMBA_CONV, MAMBA_SSD, MAMBA_GATE,
                                MOE_LATENT, MOE_SHARED)

# -- scopes of Trinity (models/trinity.py), beside the six, FFN and MOE_SHARED ---
# Read by ``benchmark/layer_metrics/decoder.py``, which keeps its own copy.
# The three nest under ATTN, so ATTN's time holds them: the attention call of
# each kind of layer (with 'flash' the Mosaic kernels and the packing around
# them) and the sigmoid gate on its output.
ATTN_WINDOW = "ps.attn/window"    # the core of a layer that sees a window of keys, rotated
# ATTN_FULL (above): here the core of a layer that sees every earlier key, not rotated
ATTN_GATE = "ps.attn/gate"        # sigmoid of the gate projection times the core's output

TRINITY_SCOPES = MOE_SCOPES + (FFN, MOE_SHARED, ATTN_WINDOW, ATTN_FULL,
                               ATTN_GATE)

# -- scopes of Mellum (models/mellum.py), beside the six and Trinity's two cores --
# Read by ``benchmark/layer_metrics/decoder.py``, which keeps its own copy.
# MOE_EXCHANGE is opened in ``ops/moe.py`` around each collective of the
# token exchange and nests under MOE_DISPATCH (rows to their experts' owners)
# and MOE_COMBINE (results back): their times hold it, forward, recomputation
# and backward alike. ATTN_FULL here is rotated by a scaled table of its own.
MOE_EXCHANGE = "ps.moe/exchange"  # the all_to_all of rows and group sizes between the chips that share a layer

MELLUM_SCOPES = MOE_SCOPES + (ATTN_WINDOW, ATTN_FULL, MOE_EXCHANGE)

# -- scopes of SDAR (models/sdar.py), beside the six and ATTN_FULL -----------------
# The six and ATTN_FULL are read by ``benchmark/layer_metrics/decoder.py``,
# which keeps its own copy; ATTN_INBLOCK has no metric of its own yet and is
# read inside ``decoder.attn_ms`` (it nests under ATTN, so ATTN's time holds
# it). ATTN_FULL here is around the two kernel calls over the clean keys: the
# clean queries under the edge a block wide, the noised ones under the strict
# edge.
ATTN_INBLOCK = "ps.attn/inblock"  # a noised query's own block of noised keys, and the merge by the logsumexps

SDAR_SCOPES = MOE_SCOPES + (ATTN_FULL, ATTN_INBLOCK)

# -- scopes of JoyAI-LLM-Flash (models/joyai.py), beside the six, FFN, MOE_SHARED --
# -- and the latent layer's three (ATTN_FULL, ATTN_LATENT, ATTN_ROPE, above) ---------
# The six, FFN, MOE_SHARED and ATTN_FULL are read by
# ``benchmark/layer_metrics/decoder.py``, which keeps its own copy; ATTN_LATENT,
# ATTN_ROPE and the two below have no metric of their own yet. MTP is around
# the whole prediction module: its ``ps.attn``, ``ps.moe/*`` and ``ps.head``
# open inside it under their own names and are read as those; MTP_JOIN nests
# under MTP and, having no scope of the reader's around it, is read with the
# gradient's rest (the embedding, norms and residuals outside the scopes).
MTP = "ps.mtp"                    # the prediction module for the token after next: join, one layer, norm, the shared head
MTP_JOIN = "ps.mtp/join"          # the two norms, the second embedding lookup, eh_proj

JOYAI_SCOPES = MOE_SCOPES + (FFN, MOE_SHARED, ATTN_FULL, ATTN_LATENT,
                             ATTN_ROPE, MTP, MTP_JOIN)

# -- scopes of Granite-4.0-H (models/granite_h.py): no expert, so not the six -------
# A dense decoder of two parts a layer: the mixer under MAMBA (with its three
# inner scopes) or ATTN, the SwiGLU every layer has under FFN, the tied head
# under HEAD. All but MAMBA_GATE are read by
# ``benchmark/layer_metrics/decoder.py``, which keeps its own copy.
GRANITE_SCOPES = (ATTN, HEAD, FFN, MAMBA, MAMBA_CONV, MAMBA_SSD, MAMBA_GATE)

# -- scopes of Qwen3-Next (models/qwen3_next.py), beside the six and MOE_SHARED -------
# All read by ``benchmark/layer_metrics/decoder.py``, which keeps its own copy,
# but ATTN_ROPE (inside ``decoder.attn_ms``). The delta-rule mixer is under
# KDA with Kimi-Linear's two inner scopes; the attention layer under ATTN
# with ATTN_ROPE around the partial rotation, ATTN_FULL around the kernel call
# and ATTN_GATE around the sigmoid gate that the q projection carries; the
# shared expert and its own sigmoid gate under MOE_SHARED.
QWEN3_NEXT_SCOPES = MOE_SCOPES + (KDA, KDA_CONV, KDA_CORE, MOE_SHARED,
                                  ATTN_FULL, ATTN_ROPE, ATTN_GATE)

# -- scopes of Ouro (models/ouro.py): ATTN, FFN and HEAD as the dense decoders ----------
# LOOP is around all that runs ``total_ut_steps`` times on the same weights:
# the stack's layers, with their ATTN and FFN inside it, and the final norm
# that closes a pass. HEAD, around the one blocked readout of all the passes
# behind the loop, and EXIT, around the passes' gates, the exit distribution,
# its entropy and the weighting of the passes' losses, are opened beside it,
# not inside: LOOP, HEAD and EXIT are disjoint. ATTN, FFN and HEAD are read by
# ``benchmark/layer_metrics/decoder.py``, LOOP and EXIT by
# ``benchmark/layer_metrics/ouro.py``; each keeps its own copy.
LOOP = "ps.loop"                  # the passes: every layer application and the final norm of each pass
EXIT = "ps.exit"                  # the passes' exit gates, the exit distribution, its entropy, the weighted sum

OURO_SCOPES = (ATTN, HEAD, FFN, LOOP, EXIT)

# -- scopes of Phi-4-mini-flash (models/phi4flash.py): no expert, so not the six ----------
# A decoder whose second half reads its first half's memory. ATTN, HEAD, FFN,
# MAMBA, MAMBA_CONV and the two cores ATTN_WINDOW and ATTN_FULL mean what they
# mean above and are read by ``benchmark/layer_metrics/decoder.py``, which
# keeps its own copy. The four below have no metric yet (``BENCHMARK.json``
# holds its 128 ``per_layer`` entries; ``PERF.md`` section 7 names the
# metrics that will read them) and are read from a traced run's stderr:
# MAMBA_S6 nests under MAMBA and ATTN_CROSS and ATTN_DIFF under ATTN, so those
# two metrics hold them; GMU is a mixer of its own and no scope of the
# reader's is around it, so it is read with the gradient's rest.
MAMBA_S6 = "ps.mamba/s6"          # ops/selective_scan.py alone: Mamba-1's scan, a decay a channel and a state
GMU = "ps.gmu"                    # the gated memory unit: in projection, the gate by another layer's scan output, out projection
ATTN_CROSS = "ps.attn/cross"      # the core of a layer that reads another layer's K and V
ATTN_DIFF = "ps.attn/diff"        # differential attention's combine: lambda, the subtraction, the head norm, the scale (f32 elementwise)

PHI4FLASH_SCOPES = (ATTN, HEAD, FFN, MAMBA, MAMBA_CONV, ATTN_WINDOW,
                    ATTN_FULL, MAMBA_S6, GMU, ATTN_CROSS, ATTN_DIFF)

# -- host spans (Tracer.program_span) -----------------------------------------
STEP_RUN = "step.run"                      # the whole of run(batch); step=n
STEP_LAUNCH = "step.launch"                # the jitted call only; child of step.run; step, in_flight[, drained_at_most_ms]
INPUT_PLACE = "input.place"                # place(item) in device_prefetch; seq, nbytes
INPUT_SOURCE_WAIT = "input.source_wait"    # the consumer's q.get(); seq
INPUT_PRODUCE = "input.produce"            # next(batches) in the producer thread; seq

HOST_SPANS = (STEP_RUN, STEP_LAUNCH, INPUT_PLACE, INPUT_SOURCE_WAIT,
              INPUT_PRODUCE)

# -- the step's pace (obs/pace.py, called by kv/fused.py::run) ------------------
# Whether the chip was waiting for a launch. Two arguments of ``step.launch``,
# read by ``benchmark/layer_metrics/pace.py``, which keeps its own copy; a
# gauge and two counters of ``obs.default_registry()`` and one flight event,
# an operator's, when the ring has turned over.
IN_FLIGHT = "in_flight"                    # steps of this wrapper launched and not yet seen finished, at the launch
DRAINED_AT_MOST_MS = "drained_at_most_ms"  # on a drained launch (in_flight 0, not the first): ms since the launch before it began
STEP_IN_FLIGHT = "ps_step_in_flight"       # gauge: in_flight at the last launch
STEP_DRAINED_LAUNCHES = "ps_step_drained_launches_total"  # counter: launches with in_flight 0 but the first
STEP_SLOW = "ps_step_slow_total"           # counter: slow_step events
SLOW_STEP = "slow_step"                    # flight event; step, ms, median_ms, in_flight

# -- set-up spans (Tracer.program_span; a dozen a process, none per step) ------
# Read by ``benchmark/layer_metrics/setup.py``, which keeps its own copy.
SETUP_IMPORT = "setup.import"          # the import of the ps_tpu package, first line to last
SETUP_INIT = "setup.init"              # ps.init whole; backend, devices
SETUP_STORE_INIT = "setup.store_init"  # KVStore.init; leaves, nbytes
SETUP_TABLE_INIT = "setup.table_init"  # SparseEmbedding.init; rows, dim, nbytes

SETUP_SPANS = (SETUP_IMPORT, SETUP_INIT, SETUP_STORE_INIT, SETUP_TABLE_INIT)

# -- the compiler's own events as spans (obs/compiles.py) ----------------------
# Recorded when jax says the interval is over, with t0 = now - duration, on
# the thread that asked for the program and as a child of the program span
# that is open on that thread's program stack (step 0's ``step.launch``; a
# set-up span; none). Trace events nest (``matmul`` inside ``my_step``) and
# the cache's load lies inside the backend's interval: read unions, not sums.
COMPILE_TRACE = "compile.trace"            # Python to jaxpr; fun
COMPILE_LOWER = "compile.lower"            # jaxpr to an MLIR module; fun
COMPILE_BACKEND = "compile.backend"        # XLA's compile, or the load from the persistent cache; fun, cache
COMPILE_CACHE_LOAD = "compile.cache_load"  # the retrieval alone, inside compile.backend on a hit; fun

COMPILE_SPANS = (COMPILE_TRACE, COMPILE_LOWER, COMPILE_BACKEND,
                 COMPILE_CACHE_LOAD)
