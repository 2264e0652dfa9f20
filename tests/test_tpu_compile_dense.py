"""Ask the TPU's compiler, without a chip: the dense model, flash, Granite.

The file with the most cases, so the one xdist starts first: the 2b7 train
step on one chip and on the four of a v5e:2x2 (what it plans, what it says
to whom, how its tensor-parallel traffic is hidden), the 7B step's memory on
eight, the serving engine's programs and the paged decode kernel; every
flash call (the presets' widths, the cells' walks, the banded plans, the
face the readers know the calls by); and, riding here because a whole-step
compile that starts early runs beside tests one core wide, the Granite
cell's step with its mixer's passes and its scan (``models/hybrid.py``).

Compiles against a described (device-less) v5e; ``tests/described_tpu.py``
has the fixtures, the helpers and the rule that put each case where it is.
"""

import pytest

from described_tpu import (V5E_HBM, _compile_cell_step, _entry_ops, _sds,
                           _while_bodies, _with_shardings, describe)


def test_granite_step_keeps_the_parents_list(topo, on_chip_branch,
                                             monkeypatch):
    """The Granite cell's step has no room (the estimate reads 15.57e9 of
    the 14.37e9 the rule leaves): the plan keeps nothing more, the program
    plans no more than it did with passes twice the even share
    (15,310,881,280 bytes at PR 42; 14,993,509,376 when this was written)
    and XLA rematerializes nothing of its own."""
    compiled, plan, said = _compile_cell_step(
        "train-granite4hs-ep8-s8192-b2", topo, monkeypatch)
    assert [(p["kept"], p["kept_bytes"], p["why"]) for p in said] == [
        ("", 0, "no room")]
    assert 13.0e9 < plan <= 15_310_881_280, plan
    assert compiled.as_text().count(".remat") == 0


def test_a_mixers_passes_at_granite_widths(one_chip, on_chip_branch):
    """One mixer's forward, its replay under ``jax.checkpoint`` and its
    backward at the Granite cell's widths (B2 x S8192), compiled for the
    chip: what tells a later refactor that it brought a pass back. The
    Mosaic calls are the scan's and nobody else's (forward twice 5 -> 2,
    backward 7 -> 5: ``readers/granite_kernel_roofline.py`` raises on any
    other). No op leaves a float32 array of the rows' size behind. The
    non-matmul fusions move 7.85 GB (15.4 at the parent of PR 33, whose
    gradient jax transposed), under the rules' own account; ops outside
    every fusion 0.54 GB, two slices of x out of the projection's output
    (4.1: two broadcasts of dt over a head's lanes, a relayout of ``du x``
    before its sum over them)."""
    import re

    import jax
    import jax.numpy as jnp

    from benchmark.readers import kernel_roofline
    from ray_tpu.models import hybrid

    bf = jnp.bfloat16
    cfg = hybrid.HybridConfig(
        vocab_size=12544, d_model=4096, n_layers=1, n_heads=32, n_kv_heads=8,
        d_ff=768, n_experts=72, top_k=10, experts_held=(9, 0),
        shared_d_ff=1536, mamba_heads=128, mamba_head_dim=64,
        mamba_state=128, mamba_conv=4, mamba_chunk=256, ssd_impl="pallas",
        layer_types=("mamba",), dtype=bf, param_dtype=bf,
        residual_multiplier=0.22)
    B, S = 2, 8192
    stack = jax.eval_shape(
        lambda: hybrid.init_params(jax.random.PRNGKey(0), cfg))["layers"][0]
    lp = {k: _sds(stack[k].shape[1:], bf, one_chip) for k in (
        "mix_norm", "in_proj", "conv_w", "conv_b", "dt_bias", "a_log",
        "d_skip", "gate_norm", "out_proj")}

    def loss(x, lp):
        y = jax.checkpoint(lambda x, lp: hybrid.mixer_half(
            x, lp, cfg, "mamba"))(x, lp)
        return jnp.sum(y.astype(jnp.float32) ** 2)   # wants the forward too

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        _sds((B, S, cfg.d_model), bf, one_chip), lp).compile().as_text()
    calls = sorted(kernel_roofline.signature(ln) for ln in text.splitlines()
                   if kernel_roofline.signature(ln) is not None)
    assert calls == [(2, 5), (2, 5), (5, 7)], calls
    ops = _entry_ops(text)
    rows = re.compile(rf"f32\[{B},{S},({cfg.mamba_inner}|"
                      rf"{cfg.mamba_inner + 2 * cfg.mamba_state})\]")
    wide = [(op, result[:200]) for op, result, *_ in ops if rows.search(result)]
    assert not wide, wide
    passes = sum(r + w for op, _, r, w, matmul in ops
                 if op == "fusion" and not matmul)
    plan = hybrid.plan(cfg, B, S)
    assert passes < 8.2e9, passes
    assert passes < 2 * plan["hbm_bytes_fwd"] + plan["hbm_bytes_bwd"] \
        < 12.5e9, plan
    alone = sum(w for op, _, _, w, _ in ops if w > 50e6 and op in (
        "copy", "slice", "broadcast", "convert", "transpose", "pad",
        "concatenate"))
    assert alone < 1.0e9, alone


def test_ssd_scan_compiles_at_granite_widths(one_chip, on_chip_branch):
    """The state-space scan's two Mosaic calls at Granite-4.0-H-Small's
    shapes (B2 x S8192, 128 heads of 64, state 128, chunks of 256): the
    forward with the states it hands the backward, and the backward."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.ssd import ssd_scan

    B, S, H, P, N = 2, 8192, 128, 64, 128
    bf, f32 = jnp.bfloat16, jnp.float32
    args = (_sds((B, S, H, P), bf, one_chip), _sds((B, S, H), f32, one_chip),
            _sds((H,), f32, one_chip), _sds((B, S, N), bf, one_chip),
            _sds((B, S, N), bf, one_chip))

    def loss(*a):
        return ssd_scan(*a, chunk=256, impl="pallas").astype(f32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *args).compile().as_text()
    assert text.count("tpu_custom_call") == 2, text[:2000]
    assert "f32[2,32,8192,128]" in text          # the chunks' incoming states


def _lower_train_step(mesh, rules, batch, seq, cfg=None, opt=None):
    """A llama train step lowered for ``mesh`` from shapes alone: by
    default 2b7 as chip_smoke.py trains it (bf16 params, flash, remat,
    bf16 logits, adafactor)."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import llama
    from ray_tpu.parallel.train_step import (batch_sharding,
                                             make_train_state_init,
                                             make_train_step)

    if cfg is None:
        cfg = llama.PRESETS["2b7"].replace(
            dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, remat=True,
            attn_impl="flash", f32_logits=False)
    if opt is None:
        opt = optax.adafactor(3e-4)
    init_fn, state_sh = make_train_state_init(
        lambda k: llama.init_params(k, cfg), opt, mesh, rules,
        llama.param_specs(cfg))
    state = _with_shardings(
        jax.eval_shape(init_fn, jax.random.PRNGKey(0)), state_sh)
    bshape = {"tokens": jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32)}
    batch_abs = _with_shardings(bshape, batch_sharding(mesh, rules, bshape))
    step = make_train_step(
        lambda p, b: llama.loss_fn(p, b, cfg, mesh=mesh, rules=rules),
        opt, mesh, rules, state_sh, batch_shapes=bshape)
    return step.lower(state, batch_abs)


_STEPS = {}       # compiled 2b7 steps, shared by the tests of one shape


def _step_2b7(topo, chips, tp=2):
    """The compiled 2b7 train step on one chip (B5 x S1024, dp) or on the
    four of a v5e:2x2 (B8 x S1024, MeshSpec(fsdp=2, tp=2) or
    MeshSpec(tp=4), fsdp_tp)."""
    from ray_tpu.parallel import MeshSpec, ShardingRules, build_mesh

    if (chips, tp) not in _STEPS:
        if chips == 1:
            mesh = build_mesh(MeshSpec(dp=-1), devices=topo.devices[:1])
            lowered = _lower_train_step(mesh, ShardingRules.dp(), 5, 1024)
        else:
            mesh = build_mesh(MeshSpec(fsdp=4 // tp, tp=tp),
                              devices=topo.devices)
            assert len({d.id for d in mesh.devices.flat}) == 4
            lowered = _lower_train_step(mesh, ShardingRules.fsdp_tp(), 8,
                                        1024)
        _STEPS[chips, tp] = lowered.compile()
    return _STEPS[chips, tp]


COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def _rotary_lines(text, head_dim=80):
    """The lines of a compiled 2b7 step (heads of 80) issued under the
    scope ``rotary``, having checked that the rotary is one product a
    tensor with the pairing's signed swap (``llama.apply_rope``): in every
    pass a convolution under that scope, and NO array anywhere in the step
    whose last axis is half a head (at heads of 128 the split / concatenate
    form handed four such halves of q and k from one fusion and the
    backward copied them in float32: PERF.md 5, l8; at these heads of 80
    XLA kept the halves inside its fusions, so here the products' scope is
    what tells the forms apart)."""
    import re

    halves = sorted(set(re.findall(
        rf"\w+\[(?:\d+,)+{head_dim // 2}\]", text)))
    assert not halves, halves
    lines = [ln for ln in text.splitlines() if "/rotary/" in ln]
    products = [ln for ln in lines if " convolution(" in ln]
    for under in ("jvp(layers)", "rematted_computation",
                  "transpose(jvp(layers))"):
        assert [ln for ln in products if under in ln], under
    return lines


def _loop_permutes(text, rows_shape):
    """[(loop body, permute, matmul fusions between its start and its
    done)] for every collective-permute of ``rows_shape`` (a shape, or a
    scope on the permute's ``op_name``) in the layer loops of a compiled
    step, having checked that the loops hold no blocking all-reduce of an
    activation and that nothing joins or splits the rows as an op of its
    own (a copy of every part)."""
    import re

    bodies = _while_bodies(text)
    assert len(bodies) == 2, sorted(bodies)           # forward, backward
    permutes = []
    for name, lines in bodies.items():
        alone = [ln for ln in lines if re.search(
            r"= \w+\[\d+,\d+,\d+[\],]\S* (all-reduce|concatenate|select|"
            r"dynamic-update-slice)\(", ln)]
        assert not alone, (name, alone)
        starts = {}
        for at, ln in enumerate(lines):
            head = ln.split(" = ")[0]
            if " collective-permute-start(" in ln and rows_shape in ln:
                starts[head] = at
            done = re.search(r" collective-permute-done\((%[\w.\-]+)\)", ln)
            if done and done.group(1) in starts:
                under = lines[starts[done.group(1)] + 1:at]
                permutes.append((name, head, sum(
                    "convolution" in u.split(" = ")[0] or "kind=kOutput" in u
                    for u in under)))
    return permutes


def test_four_shard_ring_overlaps_most_of_its_traffic(topo, on_chip_branch):
    """The ring of n - 1 permutes at tp=4, which no cell runs: three
    quarter-row permutes for each of tp=2's one, no all-reduce, no join as
    an op of its own, the kernels still there. What the scheduler leaves
    bare is pinned as found: 3 of the 33 (PERF.md 7), the last step of the
    feed-forward's gather and of its scatter forward (the own rows'
    ``w_down`` product is hoisted above the transfer it was to cover) and
    one gather of the backward."""
    compiled = _step_2b7(topo, 4, tp=4)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3
    permutes = _loop_permutes(text, "bf16[8,256,2560]")   # B8, S1024 / 4, D
    assert len(permutes) == 33, permutes
    bare = [p for p in permutes if p[2] == 0]
    assert len(bare) <= 3, bare


def test_2b7_fsdp_tp_flash_step_compiles_on_four_chips(topo, on_chip_branch):
    """The README's first example with the kernel the one-chip numbers
    rest on: GSPMD cannot partition a Mosaic call, so this compiles only
    while models/llama.py wraps it in a shard_map, and only while
    adafactor's rank-1 state gets a valid sharding."""
    compiled = _step_2b7(topo, 4)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM, mem
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3
    assert "all-gather" in text and ("reduce-scatter" in text
                                     or "all-reduce" in text)
    # the rotary contracts a head's lanes, which no axis shards: it brought
    # no collective (the permutes' counts below hold what the step has)
    assert not [ln for ln in _rotary_lines(text)
                if any(c + "(" in ln or c + "-start(" in ln
                       for c in COLLECTIVES)]


def test_four_chip_step_overlaps_its_tensor_parallel_traffic(topo,
                                                             on_chip_branch):
    """Under a tensor axis the layer loops hold no blocking all-reduce of
    an activation: each became half-row collective-permutes
    (parallel/collective_matmul.py), 4 a layer forward and 7 backward, and
    the scheduler put a matmul fusion between every start and its done."""
    # B8 / fsdp 2, S1024 / tp 2, D
    permutes = _loop_permutes(_step_2b7(topo, 4).as_text(),
                              "bf16[4,512,2560]")
    assert len(permutes) == 11, permutes
    assert all(matmuls >= 1 for _, _, matmuls in permutes), permutes


def test_four_chip_step_scatters_its_weight_gradients_by_permutes(
        topo, on_chip_branch):
    """Under ``fsdp`` beside the tensor axis a layer's seven weights enter
    the helpers as they are stored and their gradients leave so: the
    backward loop's body holds no ``all-reduce-scatter`` fusion (XLA's
    blocking form of jax's psum and the slice after it; the head's and the
    embedding's, outside the helpers and the loop, stay), each gradient
    travels as ONE permute of the half that belongs to the other shard
    (``tp.gradient``), the scheduler put a matmul fusion between every
    start and its done, and the halves in flight fit (the four-chip cell's
    own plan: 14,573,366,784 bytes of a v5e's 16,909,336,064; PERF.md 6)."""
    compiled = _step_2b7(topo, 4)
    text = compiled.as_text()
    bodies = _while_bodies(text)
    assert not [ln.split(" = ")[0] for lines in bodies.values()
                for ln in lines if "all-reduce-scatter" in ln]
    assert "all-reduce-scatter" in text                   # the head's
    permutes = _loop_permutes(text, "/tp.gradient/")
    assert len(permutes) == 7, permutes
    assert len({body for body, _, _ in permutes}) == 1    # the backward's
    assert all(matmuls >= 1 for _, _, matmuls in permutes), permutes
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes - mem.alias_size_in_bytes < 14.8e9, mem


def test_2b7_train_step_fits_one_chip(topo, on_chip_branch):
    compiled = _step_2b7(topo, 1)
    mem = compiled.memory_analysis()
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert need < V5E_HBM, mem
    # forward, dq, dkdv: the checkpoint keeps the kernel's output, so the
    # compiled backward holds no second forward call
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3
    assert _rotary_lines(text)


def test_one_chip_step_has_no_collective(topo, on_chip_branch):
    """No tensor axis, no plan: the one-chip program talks to nobody."""
    text = _step_2b7(topo, 1).as_text()
    assert not [c for c in COLLECTIVES
                if c + "(" in text or c + "-start(" in text]
    assert _rotary_lines(text)


def test_llama7b_fsdp_fits_v5e8_hbm(topo, no_persistent_cache):
    """HBM feasibility of BASELINE.md target 2: the 7B train step (f32
    master weights, adamw with a bf16 first moment, XLA attention, B8 x
    S2048) sharded ``fsdp=8`` over a described v5e:2x4 (``topo`` has shown
    by then that this process can describe one). The compiler enforces
    the 16 GB budget (a program that does not fit fails with
    RESOURCE_EXHAUSTED) and reports the peak memory of a device."""
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies

    from ray_tpu.models import llama
    from ray_tpu.parallel import MeshSpec, ShardingRules, build_mesh

    v5e8 = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x4")
    mesh = build_mesh(MeshSpec(fsdp=8), devices=v5e8.devices)
    cfg = llama.PRESETS["7b"].replace(
        dtype=jnp.bfloat16, remat=True, attn_impl="xla", f32_logits=False,
        max_seq_len=2048)
    assert llama.num_params(cfg) > 6.5e9
    opt = optax.adamw(3e-4, weight_decay=0.01, mu_dtype=jnp.bfloat16)
    compiled = _lower_train_step(mesh, ShardingRules.fsdp(), 8, 2048,
                                 cfg=cfg, opt=opt).compile()
    assert compiled.memory_analysis().peak_memory_in_bytes <= V5E_HBM


def test_2b7_engine_programs_compile(one_chip, on_chip_branch):
    """The serving engine's own jitted programs at 2b7 widths: one paged
    decode block (must hold the paged Pallas kernel) and one prefill
    bucket. Params are shapes; the pool the engine allocates is tiny, the
    pool the programs are lowered for is the real one."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.serve.llm import LLMEngine

    cfg = llama.PRESETS["2b7"].replace(param_dtype=jnp.bfloat16,
                                       max_seq_len=1024)
    slots, ps, maxP = 8, 64, 16
    params = jax.tree.map(
        lambda s: _sds(s.shape, s.dtype, one_chip),
        jax.eval_shape(lambda: llama.init_params(jax.random.PRNGKey(0),
                                                 cfg)))
    eng = LLMEngine(cfg=cfg, params=params, max_slots=slots,
                    kv_layout="paged", page_size=ps, num_pages=2)
    pool = _sds((cfg.n_layers, cfg.n_kv_heads, slots * maxP + 1, ps,
                 cfg.head_dim), jnp.bfloat16, one_chip)
    i32 = lambda *shape: _sds(shape, jnp.int32, one_chip)  # noqa: E731
    decode = eng._decode_n_paged.lower(
        params, i32(slots, 1), pool, pool, i32(slots, maxP), i32(slots),
        i32(slots), _sds((slots,), jnp.float32, one_chip),
        _sds((2,), jnp.uint32, one_chip), n=8).compile()
    mem = decode.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM, mem
    assert "tpu_custom_call" in decode.as_text()
    prefill = eng._prefill.lower(params, i32(1, 512), i32(1)).compile()
    mem = prefill.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM, mem


# (slots, heads, kv_heads, head_dim, page_size, pages per slot)
PAGED_WIDTHS = {
    "2b7": (8, 20, 20, 128, 64, 16),
    "1b": (8, 16, 8, 128, 64, 32),
}


@pytest.mark.parametrize("preset", sorted(PAGED_WIDTHS))
def test_paged_decode_kernel_compiles(preset, one_chip, on_chip_branch):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.paged_attention import paged_decode_attention_inplace

    S, H, KV, HD, ps, maxP = PAGED_WIDTHS[preset]
    pool = _sds((KV, S * maxP + 1, ps, HD), jnp.bfloat16, one_chip)
    args = (_sds((S, H, HD), jnp.bfloat16, one_chip),
            _sds((S, KV, HD), jnp.bfloat16, one_chip),
            _sds((S, KV, HD), jnp.bfloat16, one_chip), pool, pool,
            _sds((S, maxP), jnp.int32, one_chip),
            _sds((S,), jnp.int32, one_chip))
    text = jax.jit(paged_decode_attention_inplace,
                   donate_argnums=(3, 4)).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, text[:2000]


# (batch, seq, heads, kv_heads, head_dim, stated scale) of the attention
# call one device makes in each of the benchmark's five cells
CELL_FLASH = {
    "train-deepseek7b-l8": (3, 4096, 32, 32, 128, None),
    "train-deepseek7b-fsdp2tp2": (2, 4096, 16, 16, 128, None),
    "train-olmoe1b7b-s4096-b4": (4, 4096, 16, 16, 128, None),
    "train-granite4hs-ep8-s8192-b2": (2, 8192, 32, 8, 128, 0.0078125),
    "train-glm47flash-ep8-s8192-b2": (2, 8192, 20, 20, 256, None),
    # Mellum2's two kinds of layer (a seventh entry: the window)
    "train-mellum2-ep4-s16384-b1/full": (1, 16384, 32, 4, 128, None),
    "train-mellum2-ep4-s16384-b1/window": (1, 16384, 32, 4, 128, None, 1024),
    # Command A+'s two kinds: 16 query heads a KV head, a window of 4,096
    "train-commandaplus-ep16-s8192-b1/full": (1, 8192, 32, 2, 128, None),
    "train-commandaplus-ep16-s8192-b1/window": (1, 8192, 32, 2, 128, None,
                                                4096),
}


# where a window is so wide that only the forward is banded: (path, span,
# in flight) of the forward and of dQ; K and V of a head are 2 MiB each at
# S 8,192 x D 128, so dQ loops over the whole head and skips in the kernel,
# and the dK/dV call is resident
WIDE_WINDOW = {4096: [("band", 4608, 1), ("loop", 8192, 2)]}


@pytest.mark.parametrize("cell", sorted(CELL_FLASH))
def test_the_cells_flash_walks_fit_the_vmem_a_call_gets(cell, one_chip,
                                                       on_chip_branch):
    """The forward and the dQ call of every cell compile for the chip in
    the 16 MiB a Mosaic call gets that asks for no more (``kv_plan``'s
    span and blocks in flight are chosen against it; a call's
    ``vmem_limit_bytes`` is taken out of XLA's fast memory): their scoped
    VMEM in the compiled text is the default. Only the resident dK/dV
    plan asks (``bwd_dkdv_plan``). The Mellum2 cell's window layers take
    the banded plans: a q-block's whole band of three k-blocks in one grid
    step, fetched where it starts, and a dK/dV q axis of a k-block's three
    q-blocks; its full layers and the GLM cell stream, a span of blocks a
    grid step in all three calls (PR 49: the dK/dV call too)."""
    import re
    import sys

    import jax
    import jax.numpy as jnp

    from benchmark.readers import kernel_roofline
    from ray_tpu.ops.flash_attention import flash_attention

    fa = sys.modules["ray_tpu.ops.flash_attention"]
    B, S, H, KV, D, scale, *window = CELL_FLASH[cell]
    window = window[0] if window else None
    q = _sds((B, S, H, D), jnp.bfloat16, one_chip)
    kv = _sds((B, S, KV, D), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v, scale=scale, window=window).astype(
            jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    scoped = {}
    for ln in text.splitlines():
        sig = kernel_roofline.signature(ln)
        if sig is not None:
            size = re.search(
                r'"scoped_memory_configs":\[\{[^}]*"size":"(\d+)"', ln)
            # no call of the program asks: the list is empty in all
            scoped[kernel_roofline.FLASH[sig]] = int(
                size.group(1)) if size else fa._SCOPED_VMEM_BYTES
    assert sorted(scoped) == ["dkdv", "dq", "fwd"], scoped
    assert scoped["fwd"] == scoped["dq"] == fa._SCOPED_VMEM_BYTES, scoped
    # K and V of a head are 16 MiB double-buffered at S 8192 x D 256 and
    # at S 16384 x D 128: both stream, and so does every windowed call
    streams = S * D == 8192 * 256
    assert (scoped["dkdv"] > fa._SCOPED_VMEM_BYTES) == (not streams), scoped
    plans = {c: fa.kv_plan(S=S, T=S, D=D, dtype=jnp.bfloat16, block_q=512,
                           block_k=512, window=window or 0, call=c)
             for c in ("fwd", "dq")}
    want = [("loop", S, 1), ("loop", S, 2)]
    grids = [(S // 512, S // 512)] * 2      # a head's grid steps, at work
    written = [False, False]                # whole spans straight-line
    if streams:         # half a head's keys a grid step, two a q-block
        want = [("stream", S // 2, 2),
                ("stream", S // 2, 1 if D == 256 else 2)]
        grids = [(2 * S // 512, 3 * S // 1024)] * 2
        # what is written out: the dQ call's spans of 8 blocks at a head
        # of 256 (the forward's ask for 19 MiB that way), the forward's at
        # a head of 128, cut to 8 blocks for it (4 spans a q-block)
        written = [D == 128, D == 256]
        if D == 128:
            want[0], grids[0] = ("stream", S // 4, 2), (4 * S // 512, 80)
    if window:          # a q-block's band of three k-blocks in one step
        want = WIDE_WINDOW.get(window, [("band", window + 512, 3)] * 2)
        grids = [(S // 512, S // 512)] * 2
        written = [False, False]
    assert [(p["path"], p["span"], p["in_flight"])
            for p in plans.values()] == want, plans
    assert [(S // 512 * p["steps"], p["band_steps"])
            for p in plans.values()] == grids, plans
    assert [p["written"] for p in plans.values()] == written, plans
    assert [p["whole_steps"] > 0 for p in plans.values()] == written, plans
    dkdv = fa.bwd_dkdv_plan(
        S=S, T=S, D=D, dtype=jnp.bfloat16, groups=H // KV, block_q=512,
        block_k=512, causal=True, window=window or 0,
        vmem_bytes=fa._V5E_VMEM_BYTES)
    # (path, a head's grid steps, those at work): the causal triangle in
    # spans of 4 q-blocks (a head of 256) or 8 (of 128) a k-block, or three
    # q-blocks a k-block and the sequence's end
    assert (dkdv["path"], S // 512 * dkdv["steps"], dkdv["band_steps"]) == (
        ("band", 96, 93) if window and window not in WIDE_WINDOW else
        ("stream", 64, 40) if streams and D == 256 else
        ("stream", 128, 80) if streams else
        ("resident", S // 512, S // 512)), dkdv
    if dkdv["path"] == "stream":
        assert (dkdv["span"], dkdv["in_flight"]) == (
            (2048, 1) if D == 256 else (4096, 1)), dkdv
        assert dkdv["walk_bytes"] <= fa._SCOPED_VMEM_BYTES
    # the compiled calls carry the scope of the plan they took
    for call, plan in (("fwd", plans["fwd"]), ("dq", plans["dq"]),
                       ("dkdv", dkdv)):
        assert f"flash.{call}.{plan['path']}" in text, (call, plan["path"])


# (batch, seq, heads, kv_heads, head_dim) of the attention call each
# preset's train step makes (2b7: chip_smoke.py's batch)
FLASH_WIDTHS = {
    "2b7": (5, 1024, 20, 20, 128),
    "debug-125m": (8, 1024, 12, 12, 64),
    "1b": (4, 2048, 16, 8, 128),
    # the benchmark's train cells: the dK/dV call's resident plan at the
    # VMEM limit its estimate asks for (Mosaic planned 14 MiB of it here)
    "deepseek-7b-s4096": (3, 4096, 32, 32, 128),
    # the longest sequence the forward kernel holds, grouped: f32 results
    "gqa-s8192": (1, 8192, 32, 8, 128),
}


@pytest.mark.parametrize("preset", sorted(FLASH_WIDTHS))
def test_flash_forward_backward_compiles(preset, one_chip, on_chip_branch):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.flash_attention import flash_attention

    B, S, H, KV, D = FLASH_WIDTHS[preset]
    q = _sds((B, S, H, D), jnp.bfloat16, one_chip)
    kv = _sds((B, S, KV, D), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    # forward + dq + dkdv kernels
    assert text.count("tpu_custom_call") >= 3, text[:2000]


@pytest.mark.parametrize("window,fwd,dq", [
    (1024, ("band", 1536, 3), ("band", 1536, 2)),
    (2048, ("band", 2560, 1), ("band", 2560, 1)),
    (4096, ("band", 4608, 1), ("stream", 4096, 1))],
    ids=["w1024", "w2048", "w4096"])
def test_banded_calls_at_a_head_of_256_fit_the_vmem_a_call_gets(
        window, fwd, dq, one_chip, on_chip_branch):
    """No cell has a window at a head of 256 (Gemma-2's shape), where a
    block in flight and the dQ call's sum are twice a head of 128's: the
    three calls compile in the 16 MiB a call gets on the plans ``kv_plan``
    takes there (the band's three blocks all in flight passed it by 1 MiB
    in the dQ call, and a band of nine with one: two in flight, and T's
    spans for the nine)."""
    import sys

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.flash_attention import flash_attention

    fa = sys.modules["ray_tpu.ops.flash_attention"]
    S, D = 8192, 256
    plans = [fa.kv_plan(S=S, T=S, D=D, dtype=jnp.bfloat16, block_q=512,
                        block_k=512, window=window, call=c)
             for c in ("fwd", "dq")]
    assert [(p["path"], p["span"], p["in_flight"]) for p in plans] == [
        fwd, dq], plans
    assert all(p["walk_bytes"] <= fa._SCOPED_VMEM_BYTES for p in plans)
    q = _sds((1, S, 2, D), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v, window=window).astype(
            jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, q).compile().as_text()
    for scope in (f"flash.fwd.{fwd[0]}", f"flash.dq.{dq[0]}",
                  "flash.dkdv.band"):
        assert scope in text, scope


def test_flash_calls_keep_their_face_on_the_stream_plans(one_chip,
                                                         on_chip_branch):
    """The same face at the GLM-4.7-Flash cell's attention shape (S 8192,
    D 256), where all three calls stream by the bytes alone
    (``benchmark/readers/glm_kernel_roofline.py`` tells them as the dense
    reader does): forward 3 -> 2, dq 6 -> 1, dkdv 6 -> 2, q and k first,
    and since PR 49 every result in its own dtype (the spans add up in
    float32 VMEM scratch and the call writes once: dq, and with H == KV
    dk and dv, are bf16 where they were float32 and cast afterwards);
    each call is named after the scope of its plan."""
    import re

    import jax
    import jax.numpy as jnp

    from benchmark.readers import kernel_roofline
    from ray_tpu.ops.flash_attention import flash_attention

    B, S, H, D = 1, 8192, 2, 256
    q = _sds((B, S, H, D), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, q).compile().as_text()
    lines = {kernel_roofline.signature(ln): ln for ln in text.splitlines()
             if kernel_roofline.signature(ln) is not None}
    assert sorted(lines) == [(1, 6), (2, 3), (2, 6)], sorted(lines)
    for sig, scope in (((2, 3), "flash.fwd.stream"), ((1, 6), "flash.dq.stream"),
                       ((2, 6), "flash.dkdv.stream")):
        ln = lines[sig]
        shapes = re.findall(r"\[([\d,]+)\]", re.search(
            r"operand_layout_constraints=\{(.*?\})\}", ln).group(1))
        assert shapes[:2] == [f"{B},{H},{S},{D}"] * 2, ln[:400]
        assert f"({scope})" in re.search(r'op_name="([^"]*)"', ln).group(1)
    assert re.search(rf" = bf16\[{B},{H},{S},{D}\]", lines[(1, 6)]), \
        lines[(1, 6)][:300]
    assert re.search(rf" = \(bf16\[{B},{H},{S},{D}\]\S*, "
                     rf"bf16\[{B},{H},{S},{D}\]", lines[(2, 6)]), \
        lines[(2, 6)][:300]
    assert " f32[" not in lines[(2, 6)].split("custom-call(")[0]


def test_flash_calls_keep_their_face_in_the_trace(one_chip, on_chip_branch):
    """The roofline readers tell the three flash calls by operands and
    results alone (``benchmark/readers/kernel_roofline.py``: forward 3 -> 2,
    dq 6 -> 1, dkdv 6 -> 2, q ``[B, H, S, HD]`` and k ``[B, KV, S, HD]``
    first) and raise on any other Mosaic call in a train program. Both
    block plans of the dK/dV call have to keep that face."""
    import re
    import sys

    import jax
    import jax.numpy as jnp

    from benchmark.readers import kernel_roofline
    from ray_tpu.ops.flash_attention import flash_attention

    fa = sys.modules["ray_tpu.ops.flash_attention"]
    B, S, H, KV, D = 2, 1024, 4, 2, 128
    q = _sds((B, S, H, D), jnp.bfloat16, one_chip)
    kv = _sds((B, S, KV, D), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v, block_q=256,
                               block_k=256).astype(jnp.float32).sum()

    def calls():
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, kv, kv).compile().as_text()
        return [ln for ln in text.splitlines()
                if kernel_roofline.signature(ln) is not None]

    faces = {}
    for path, vmem in (("resident", 128 * 2 ** 20), ("stream", 2 ** 20)):
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(fa, "_vmem_bytes", lambda vmem=vmem: vmem)
            lines = calls()
        assert sorted(map(kernel_roofline.signature, lines)) == [
            (1, 6), (2, 3), (2, 6)], lines
        for ln in lines:
            # compiled text names its operands without their shapes (a
            # trace's op line has both); the layout constraints list them
            shapes = re.findall(r"\[([\d,]+)\]", re.search(
                r"operand_layout_constraints=\{(.*?\})\}", ln).group(1))
            assert shapes[:2] == [f"{B},{H},{S},{D}", f"{B},{KV},{S},{D}"], ln
        faces[path] = next(ln for ln in lines
                           if kernel_roofline.signature(ln) == (2, 6))
    # two plans, two programs: the streaming grid has one more axis
    assert faces["resident"] != faces["stream"]


def test_flash_compiles_with_a_stated_scale_and_grouped_heads(
        one_chip, on_chip_branch):
    """The attention layer of the hybrid cell: 32 heads over 8 KV heads at
    S 8192, softmax scale 1/128 in place of 128 ** -0.5."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.flash_attention import flash_attention

    q = _sds((2, 8192, 32, 128), jnp.bfloat16, one_chip)
    k = _sds((2, 8192, 8, 128), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v, scale=0.0078125).astype(
            jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, k, k).compile().as_text()
    assert text.count("tpu_custom_call") == 3, text[:2000]


LOCKFILE = ("ABORTED: Internal error when accessing libtpu multi-process "
            "lockfile")


@pytest.mark.parametrize("allowed,error,skips", [
    ("1", LOCKFILE, False), ("1", "no libtpu.so", False),
    (None, "no libtpu.so", False), (None, LOCKFILE, True)],
    ids=["allowed-lockfile", "allowed-other", "unset-other",
         "unset-lockfile"])
def test_a_process_that_cannot_describe_the_topology_fails(
        allowed, error, skips, monkeypatch):
    """``topo``'s rule (``describe``): under ``ALLOW_MULTIPLE_LIBTPU_LOAD``,
    which the driver's command sets, a worker that cannot describe the
    topology FAILS its compile tests with the error it got, whatever the
    error; a skip is left only to the library's lock with the variable
    unset, two of these files run by hand side by side."""
    from jax.experimental import topologies

    def refuse(**kw):
        raise RuntimeError(error)

    monkeypatch.setattr(topologies, "get_topology_desc", refuse)
    if allowed is None:
        monkeypatch.delenv("ALLOW_MULTIPLE_LIBTPU_LOAD", raising=False)
    else:
        monkeypatch.setenv("ALLOW_MULTIPLE_LIBTPU_LOAD", allowed)
    with pytest.raises(pytest.skip.Exception if skips else RuntimeError,
                       match="lockfile" if skips else error):
        describe("v5e:2x2")
