"""Ask the TPU's compiler, without a chip.

Every compile against a described (device-less) TPU topology lives in
THIS file: only one process may load libtpu, so a second such file would
land on another xdist worker and skip in silence. The topology and
everything built from it come from module-scoped fixtures — never at
import, never autouse — so every worker collects the same tests and only
the worker that runs this file loads the library. Compiles run in the
test's own process; nothing executes, so these say "the chip's compiler
accepts the program and it fits", never how fast or how right it is.

Kernels pick their chip branch from ``jax.default_backend()``, which is
"cpu" here: each test steers that with monkeypatch, not a program option.
"""

import os

import pytest

V5E_HBM = 16e9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-device compile is written to the persistent cache but
    cannot be read back without a chip (the next run warns and compiles
    again): keep the cache off around these compiles."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def on_chip_branch(monkeypatch, no_persistent_cache):
    """Make the kernels trace their TPU branch (Mosaic, not interpret /
    the jnp reference) although the default backend here is the CPU."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _sds(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _with_shardings(shapes, shardings):
    import jax

    return jax.tree.map(lambda s, sh: _sds(s.shape, s.dtype, sh),
                        shapes, shardings)


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


def test_flash_streams_at_a_head_of_64_and_says_its_plans(one_chip,
                                                          on_chip_branch):
    """The LFM2 cell's attention call, [1, 32 | 8, 16384, 64]: a head of
    64 is half a lane tile, a block of it a whole tile in VMEM, and the
    plans count it so (``_vmem_lanes``): forward, dQ and dK/dV take the
    stream plans of the same S at a head of 128 and compile for the chip
    in the 16 MiB a call gets without asking (counted at 64 lanes the
    dK/dV call took the resident plan and Mosaic refused its 105 MiB of
    103.5); each call is named after the scope of its plan and keeps the
    face the readers tell it by, q and k first at 64 lanes."""
    import re
    import sys

    import jax
    import jax.numpy as jnp

    from benchmark.readers import kernel_roofline
    from ray_tpu.ops.flash_attention import flash_attention

    fa = sys.modules["ray_tpu.ops.flash_attention"]
    B, S, H, KV, D = 1, 16384, 32, 8, 64
    q = _sds((B, S, H, D), jnp.bfloat16, one_chip)
    kv = _sds((B, S, KV, D), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    lines = {kernel_roofline.signature(ln): ln for ln in text.splitlines()
             if kernel_roofline.signature(ln) is not None}
    assert sorted(lines) == [(1, 6), (2, 3), (2, 6)], sorted(lines)
    for sig, scope in (((2, 3), "flash.fwd.stream"),
                       ((1, 6), "flash.dq.stream"),
                       ((2, 6), "flash.dkdv.stream")):
        ln = lines[sig]
        shapes = re.findall(r"\[([\d,]+)\]", re.search(
            r"operand_layout_constraints=\{(.*?\})\}", ln).group(1))
        assert shapes[:2] == [f"{B},{H},{S},{D}", f"{B},{KV},{S},{D}"], \
            ln[:400]
        assert f"({scope})" in re.search(r'op_name="([^"]*)"', ln).group(1)
        assert '"scoped_memory_configs":[{' not in ln      # none asks
    plans = [fa.kv_plan(S=S, T=S, D=D, dtype=jnp.bfloat16, block_q=512,
                        block_k=512, call=c) for c in ("fwd", "dq")]
    assert [(p["path"], p["span"], p["in_flight"], p["written"])
            for p in plans] == [("stream", 4096, 2, True),
                                ("stream", 8192, 2, False)], plans
    dkdv = fa.bwd_dkdv_plan(
        S=S, T=S, D=D, dtype=jnp.bfloat16, groups=H // KV, block_q=512,
        block_k=512, causal=True, window=0, vmem_bytes=fa._V5E_VMEM_BYTES)
    assert (dkdv["path"], dkdv["span"], dkdv["in_flight"]) \
        == ("stream", 4096, 1), dkdv
    assert dkdv["walk_bytes"] <= fa._SCOPED_VMEM_BYTES


def test_a_short_convolutions_pass_keeps_no_float32_rows(one_chip,
                                                        on_chip_branch):
    """One gate-taps-gate pass at the LFM2 cell's shape, [1, 16384, 6144]
    -> [1, 16384, 2048], forward with its hand-written backward: the
    compiled program's temporaries hold no float32 array as large as the
    rows (a [16384, 2048] float32 is 134 MB): three arrays in the
    activations' type, 67 MB each, the copies of B, u and dc padded by two
    rows that the taps read at three shifts (201,455,616 bytes when this
    was written)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import hybrid

    T, D = 16384, 2048
    bcu = _sds((1, T, 3 * D), jnp.bfloat16, one_chip)
    w = _sds((3, D), jnp.bfloat16, one_chip)
    g = _sds((1, T, D), jnp.bfloat16, one_chip)

    def both(bcu, w, g):
        y, back = jax.vjp(hybrid._gated_conv, bcu, w)
        return (y,) + back(g)

    compiled = jax.jit(both).lower(bcu, w, g).compile()
    mem = compiled.memory_analysis()
    # y, d_bcu and dw leave
    assert mem.output_size_in_bytes <= T * D * 4 * 2 + 65536
    assert mem.temp_size_in_bytes <= 3 * (T + 8) * D * 2 + 2 ** 20, \
        mem.temp_size_in_bytes


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


# The flash calls of the cells that do NOT stream, and the four calls of the
# attention over a set at the GLM-5.2 cell's shape: sha256[:16] of the jaxpr
# of the call and its gradient (the kernels' bodies are in it), taken at PR
# 49's PARENT (274de6d, jax JAXPRS_FROM). PR 49 rebuilt the stream plans of
# ``ops/flash_attention.py`` and moved ``_span_walk`` there; whoever changes
# those files next and means to leave a plan alone finds out here, without
# unpacking a parent (the recipe PR 39 and PR 46 ran by hand). A digest that
# moves with a change that MEANS to change the plan is replaced, and says so.
# (batch, seq, heads, kv heads, head width, stated scale, window)
JAXPRS_FROM = "0.9.0"
PARENT_FLASH_JAXPRS = {
    "train-deepseek7b-l8": (
        (3, 4096, 32, 32, 128, None, None), "a29ac8cac53c77ad"),
    "train-deepseek7b-fsdp2tp2": (
        (2, 4096, 16, 16, 128, None, None), "3dae55e1e888278a"),
    "train-olmoe1b7b-s4096-b4": (
        (4, 4096, 16, 16, 128, None, None), "79bbd65e4a7c0ae1"),
    "train-granite4hs-ep8-s8192-b2": (
        (2, 8192, 32, 8, 128, 0.0078125, None), "dae59988254d481e"),
    "train-nemotron3nano-ep8-s8192-b2": (
        (2, 8192, 32, 2, 128, None, None), "549b8bf55f644cd8"),
    "train-mellum2-ep4-s16384-b1/window": (
        (1, 16384, 32, 4, 128, None, 1024), "f0738639ded60b83"),
    "train-commandaplus-ep16-s8192-b1/full": (
        (1, 8192, 32, 2, 128, None, None), "9ac5c76f7f7ff904"),
    "train-commandaplus-ep16-s8192-b1/window": (
        (1, 8192, 32, 2, 128, None, 4096), "c64410a606bd06ce"),
    "train-glm52-ep32-s16384-b1/sparse": (None, "b598c4e3fb200c19"),
}


@pytest.mark.parametrize("cell", sorted(PARENT_FLASH_JAXPRS))
def test_the_plans_pr49_left_alone_trace_to_its_parents_programs(
        cell, on_chip_branch):
    """loop / resident / band at the seven other cells' shapes, and the
    sparse forward, head-mean probabilities, dQ and dK/dV: to the
    character."""
    import hashlib
    import re

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import sparse_attention as sa
    from ray_tpu.ops.flash_attention import flash_attention

    shape, want = PARENT_FLASH_JAXPRS[cell]
    if shape is None:
        B, S, H, D = GLM52_ATTENTION
        q = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16)
        args = (q, q, q, jax.ShapeDtypeStruct((B, S, S), jnp.int8))

        def loss(q, k, v, keep):
            o, p = sa.sparse_attention(q, k, v, keep, with_probs=True)
            return o.astype(jnp.float32).sum() + p.sum()
    else:
        B, S, H, KV, D, scale, window = shape
        kv = jax.ShapeDtypeStruct((B, S, KV, D), jnp.bfloat16)
        args = (jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16), kv, kv)

        def loss(q, k, v):
            return flash_attention(q, k, v, scale=scale, window=window
                                   ).astype(jnp.float32).sum()

    closed = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(*args)
    assert "pallas_call" in str(closed)
    if jax.__version__ == JAXPRS_FROM:
        text = re.sub(r" at 0x[0-9a-f]+", "", str(closed))
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == want


# (rows, experts, model width, one expert's width) of a cell's grouped
# matmuls: OLMoE-1B-7B's 131,072 routed rows over 64 experts of 2048 x 1024;
# GLM-4.7-Flash's one pass of 16,384 rows over the 8 experts held, 2048 x
# 1536 (no whole number of the N tiles; whole, the forward's tiles pass
# the VMEM a call gets: ``ops/grouped_matmul.py`` ``_fit``)
GMM_WIDTHS = {"olmoe": (131072, 64, 2048, 1024),
              "glm": (16384, 8, 2048, 1536),
              # Command A+'s one pass of 8,192 rows over the 8 experts held,
              # 4096 x 4096: two K tiles AND several N tiles in one call
              "commanda": (8192, 8, 4096, 4096),
              # GLM-5.2's one pass of 16,384 rows over the 8 experts held,
              # 6144 x 2048: THREE K tiles of 2,048, an expert's matrix
              # 24 MiB
              "glm52": (16384, 8, 6144, 2048),
              # Nemotron 3 Nano's one pass of 24,576 rows over the 16
              # experts held, up [2688, 1856] and down [1856, 2688]: 1,856
              # is no whole number of lanes (whole, or 1,024 + a ragged
              # 832), 2,688 three tiles of 896
              "nemotron up": (24576, 16, 2688, 1856),
              "nemotron down": (24576, 16, 1856, 2688)}


@pytest.mark.parametrize("model", sorted(GMM_WIDTHS))
def test_grouped_matmul_compiles_at_the_cells_widths(model, one_chip,
                                                     on_chip_branch):
    """The expert layer's Mosaic calls with the tiles
    ops/grouped_matmul.py names: forward, input gradient, weight gradient."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.grouped_matmul import grouped_matmul

    rows, e, d, f = GMM_WIDTHS[model]
    x = _sds((rows, d), jnp.bfloat16, one_chip)
    w = _sds((e, d, f), jnp.bfloat16, one_chip)
    sizes = _sds((e,), jnp.int32, one_chip)

    def loss(x, w, sizes):
        y = grouped_matmul(x, w, sizes, impl="pallas").astype(jnp.float32)
        return jnp.sum(y * y)               # wants the forward's product too

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        x, w, sizes).compile().as_text()
    # forward, input gradient (gmm with the matrices transposed) and
    # weight gradient (tgmm)
    assert text.count("tpu_custom_call") >= 3, text[:2000]
    assert f"bf16[{e},{d},{f}]" in text


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


def test_ssd_scan_compiles_at_nemotron_widths_in_groups(one_chip,
                                                        on_chip_branch):
    """The scan's two Mosaic calls at Nemotron 3 Nano's shapes (B2 x S8192,
    64 heads of 64 in 8 groups of B and C, state 128, chunks of 128): a
    head block is a group's 8 heads and reads its group's 128 lanes of
    [B, S, 1024]; the gradients of B and C leave a block apart."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import ssd

    B, S, H, P, N, G = 2, 8192, 64, 64, 128, 8
    bf, f32 = jnp.bfloat16, jnp.float32
    args = (_sds((B, S, H, P), bf, one_chip), _sds((B, S, H), f32, one_chip),
            _sds((H,), f32, one_chip), _sds((B, S, G, N), bf, one_chip),
            _sds((B, S, G, N), bf, one_chip))

    def loss(*a):
        return ssd.ssd_scan(*a, chunk=128, impl="pallas").astype(f32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *args).compile().as_text()
    assert text.count("tpu_custom_call") == 2, text[:2000]
    assert "f32[2,64,4096,128]" in text          # the chunks' incoming states
    assert "f32[2,8,8192,128]" in text           # dB, dC a head block
    plan = ssd.plan(S=S, H=H, P=P, N=N, chunk=128, dtype=bf, impl="pallas",
                    G=G)
    assert (plan["heads_per_block"], plan["groups"],
            plan["heads_per_group"]) == (8, 8, 8)
    assert plan["vmem_bytes"] < 16 * 2 ** 20


def _array_bytes(types: str) -> int:
    """Bytes of every array type named in ``types`` (one, or a tuple)."""
    import math
    import re

    item = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1}
    return sum(item[t] * math.prod(int(d) for d in dims.split(",") if d)
               for t, dims in re.findall(r"\b(bf16|f32|s32|u32|pred)"
                                         r"\[([\d,]*)\]", types))


def _computations(text):
    """{name: [instruction lines]} of a compiled module's computations,
    the entry under ``ENTRY``."""
    import re

    comps, cur = {}, None
    for ln in text.splitlines():
        m = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", ln)
        if m:
            cur = comps["ENTRY" if m.group(1) else m.group(2)] = []
        elif ln.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(ln.strip())
    return comps


def _entry_ops(text):
    """[(opcode, result type, bytes read, bytes written, is a matmul)] of
    the entry computation of a compiled module. A fusion reads each
    operand once, one that its body only slices at the slices' size; a
    fusion whose body holds a convolution or whose kind is kOutput is a
    matmul fusion. Any other op is listed with its result's bytes both
    ways (a copy, a slice, a broadcast, a convert left outside every
    fusion is a pass over memory of its own)."""
    import re

    comps = _computations(text)
    ops = []
    for ln in comps["ENTRY"]:
        m = re.match(r"^(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", ln)
        if not m:
            continue
        result, opcode = m.groups()
        if opcode != "fusion":
            ops.append((opcode, result, _array_bytes(result),
                        _array_bytes(result), False))
            continue
        body = comps[re.search(r"calls=%?([\w.\-]+)", ln).group(1)]
        read = 0
        for b in body:
            pm = re.match(r"^%?([\w.\-]+) = (\S+) parameter\(", b)
            if not pm:
                continue
            users = [u.split(" = ", 1)[1] for u in body if re.search(
                rf"[(, ]%?{re.escape(pm.group(1))}[,)]",
                u.split(" = ", 1)[-1])]
            sliced = [u for u in users
                      if re.match(r"\S+ (dynamic-)?slice\(", u)]
            read += sum(_array_bytes(u.split(" ")[0]) for u in sliced) \
                if users and len(sliced) == len(users) \
                else _array_bytes(pm.group(2))
        matmul = "kind=kOutput" in ln or any(" convolution(" in b
                                             for b in body)
        ops.append(("fusion", result, read, _array_bytes(result), matmul))
    return ops


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


# widths -> (T, D, F, E, K, held; a first pass's rows, a chunk of its
# gather, the loops that walk chunks, the layer's temporary bytes at most)
_HELD_WALKS = {
    # 3/2 of the even 20,480: a sixteenth is under 2,048 rows, one chunk
    # (789,279,232 bytes when this was written; 1,350,433,792 at 40,960)
    "granite": ((16384, 4096, 768, 72, 10, 9), 30720, 30720, 0,
                797_000_000),
    # 3/2 of the even 32,768 in 16 chunks (931,812,864; 1,225,576,448 at
    # the 65,536 rows of a pass twice the even share)
    "mellum2": ((16384, 2304, 896, 64, 8, 16), 49152, 3072, 2,
                941_000_000),
}


@pytest.mark.parametrize("widths", list(_HELD_WALKS))
def test_the_held_experts_walks_at_the_cells_widths(widths, one_chip,
                                                    on_chip_branch):
    """One expert layer holding a share of the experts, forward, replay
    under ``jax.checkpoint`` and backward at the Granite cell's widths (9
    of 72 experts, 16,384 tokens of 4,096) and the Mellum2 cell's (16 of
    64, 16,384 of 2,304), compiled for the chip. Where the pass gives
    chunks (Mellum2) the gather of x into expert order is a loop whose
    length the data decide, once forward and once in the replay, and its
    body holds no copy of the pass's buffer or of x (the buffer is updated
    in place); where a sixteenth is under ``HELD_CHUNK_ROWS`` (Granite
    since the pass is 3/2 of the even share) it is one op and no loop
    walks chunks. The Mosaic calls are the 22 the whole-pass gather had
    (16 ``gmm``, 6 ``tgmm``, the further passes' among them); the layer's
    temporary bytes follow the pass (three fifths to three quarters of
    what a pass twice the even share took: ``_HELD_WALKS``)."""
    import re

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import moe

    bf = jnp.bfloat16
    (T, D, F, E, K, held), want_rows, want_chunk, loops, temp = \
        _HELD_WALKS[widths]
    cfg = moe.MoEConfig(
        vocab_size=256, d_model=D, n_layers=1, n_heads=8, n_kv_heads=8,
        d_ff=F, n_experts=E, top_k=K, experts_held=(held, 0), shared_d_ff=0,
        dtype=bf, param_dtype=bf, gmm_impl="pallas")
    rows = moe.held_rows(cfg, T)
    chunk = moe.held_chunk(rows)
    assert (rows, chunk) == (want_rows, want_chunk)
    lp = {"router": _sds((D, E), bf, one_chip),
          "we_gate": _sds((held, D, F), bf, one_chip),
          "we_up": _sds((held, D, F), bf, one_chip),
          "we_down": _sds((held, F, D), bf, one_chip)}

    def loss(lp, x):
        y = jax.checkpoint(lambda lp, x: moe.feed_forward(x, lp, cfg)[0])(
            lp, x)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        lp, _sds((1, T, D), bf, one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes <= temp
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 22
    walks = {} if chunk == rows else {
        name: body for name, body in _while_bodies(text).items()
        if any(re.search(rf"\[{chunk},{D}\]", ln) for ln in body)
        and not any("tpu_custom_call" in ln for ln in body)}
    assert len(walks) == loops, sorted(walks)
    big = re.compile(rf"= \S*\[({rows}|{T}),{D}\]\S* copy\(")
    copies = [ln[:160] for body in walks.values() for ln in body
              if big.search(ln)]
    assert not copies, copies


_GLM_BLOCK = {}    # the compiled attention half, shared by its two tests


def _glm_attention_block(one_chip):
    """(cfg, B, S, compiled text, {instant: [attributes]}) of ONE latent-
    attention half at the GLM-4.7-Flash cell's widths (B2 x S8192, 20 heads
    of 192 + 64 over latents of 768 and 512): forward, replay under
    ``jax.checkpoint`` and backward, compiled for the chip."""
    import importlib

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import latent, llama

    if _GLM_BLOCK:
        return _GLM_BLOCK["block"]
    tracing = importlib.import_module("ray_tpu.ops.flash_attention").tracing
    plans, instant = {}, tracing.instant
    tracing.instant = lambda name, attrs=None, **kw: plans.setdefault(
        name, []).append(attrs)
    bf = jnp.bfloat16
    cfg = latent.LatentConfig(
        vocab_size=19360, d_model=2048, n_layers=2, n_heads=20, n_kv_heads=20,
        d_ff=1536, n_experts=64, top_k=4, experts_held=(8, 0),
        shared_d_ff=1536, q_rank=768, kv_rank=512, qk_nope_dim=192,
        qk_rope_dim=64, v_dim=256, dense_d_ff=10240, rope_theta=1e6,
        attn_impl="flash", dtype=bf, param_dtype=bf)
    B, S = 2, 8192
    stack = jax.eval_shape(
        lambda: latent.init_params(jax.random.PRNGKey(0), cfg))["layers"][0]
    lp = {k: _sds(stack[k].shape[1:], bf, one_chip) for k in (
        "attn_norm", "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm",
        "wkv_b", "wo")}
    cos, sin = llama._rope_tables(cfg.rope_theta, S, cfg.rope_dim)

    def half(x, lp):
        with jax.named_scope("attention"):      # as llama._layer opens it
            return latent.attention_half(x, lp, cfg, cos, sin)[0]

    def loss(x, lp):
        y = jax.checkpoint(half)(x, lp)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    try:
        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            _sds((B, S, cfg.d_model), bf, one_chip), lp).compile().as_text()
    finally:
        tracing.instant = instant
    _GLM_BLOCK["block"] = (cfg, B, S, text, plans)
    return _GLM_BLOCK["block"]


def test_latent_attention_block_at_glm_widths_streams_and_fits(
        one_chip, on_chip_branch, monkeypatch):
    """One latent-attention half at the GLM-4.7-Flash cell's widths,
    forward, replay under ``jax.checkpoint`` and backward, compiled for the
    chip: all three flash calls take their streaming plan by the bytes
    alone (``flash.fwd_plan``, ``flash.bwd_plan``), Mosaic accepts them in
    the VMEM a call gets without asking, and the calls keep the face the
    readers know them by (``benchmark/readers/glm_kernel_roofline.py``).
    The loop kernel at this shape is what the compiler refuses."""
    import importlib

    import jax
    import jax.numpy as jnp

    from benchmark.readers import kernel_roofline

    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    _, B, S, text, plans = _glm_attention_block(one_chip)
    calls = sorted(kernel_roofline.signature(ln) for ln in text.splitlines()
                   if kernel_roofline.signature(ln) is not None)
    assert calls == [(1, 6), (2, 3), (2, 3), (2, 6)], calls
    assert {a["path"] for a in plans["flash.fwd_plan"]} == {"stream"}
    assert plans["flash.fwd_plan"][0]["kv_block_bytes"] == 16 * 2 ** 20
    assert [plans["flash.fwd_plan"][0][n] for n in ("span", "in_flight")] == [
        4096, 2]
    back = plans["flash.bwd_plan"][0]
    assert back["path"] == "stream" and back["dq_path"] == "stream"
    assert (back["dq_span"], back["dq_in_flight"]) == (4096, 1)
    assert back["resident_bytes"] > fa._vmem_bytes() // 4
    assert plans["mla.plan"][0]["k_bytes"] == B * S * 20 * 256 * 2
    # what the bytes say, the compiler says: the loop kernel does not fit
    monkeypatch.setattr(fa, "_SCOPED_VMEM_BYTES", 2 ** 40)
    q = _sds((B, S, 20, 256), jnp.bfloat16, one_chip)
    with pytest.raises(Exception, match="(?i)vmem|memory|exceed"):
        jax.jit(lambda q, k, v: fa.flash_attention(q, k, v)).lower(
            q, q, q).compile()


def test_a_latent_attention_halfs_passes_at_glm_widths(one_chip,
                                                       on_chip_branch):
    """The same compiled block, held to what the layout bought (PR 35):
    what tells a later refactor that it brought a pass back. The
    projections write q, k and v where the kernel reads them, ``[B, H, S,
    .]`` with a head's lanes minor, so between fusions there is no result
    of 1 or 63 lanes (the rotary's rolled pairs), no float32 array of the
    heads' rotary lanes and no bf16 ``[B, S, H, nope + v]`` (K and V in one
    array). The non-matmul fusions move 1.64 GB, under the form's own
    account (``mla.plan``: 2 x ``hbm_bytes_fwd`` + ``hbm_bytes_bwd`` = 3.00
    GB); with the ops left outside every fusion (0.61 GB: the broadcast of
    the log-sum-exp over 128 lanes for the kernels, pads of the tables)
    2.25 GB, 17.6% of the parent's 12.76 GB (my compile of ``94745a7``:
    fusions 6.37, copies 3.81, slices 2.02, broadcasts 0.52, a pad 0.04;
    the layout chosen there had the sequence minor in the projections'
    results, so every view by head was a slice and a relayout copy)."""
    import re

    cfg, B, S, text, plans = _glm_attention_block(one_chip)
    H, dn, R, dv = (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                    cfg.v_dim)
    ops = _entry_ops(text)
    gone = re.compile(
        rf"\[{B},{S},{H},(1|{R - 1})\]|\[{B},{H},{S},{R - 1}\]"
        rf"|f32\[{B},({S},{H}|{H},{S}),{R}\]"
        rf"|bf16\[{B},{S},({H},{dn + dv}|{H * (dn + dv)})\]")
    back = [(op, result[:200]) for op, result, *_ in ops
            if gone.search(result)]
    assert not back, back
    passes = sum(r + w for op, _, r, w, matmul in ops
                 if op == "fusion" and not matmul)
    plan = plans["mla.plan"][0]
    assert (plan["rope"], plan["kv"]) == ("projected", "split_weights")
    assert plan["extra_columns"] == 21 * 64
    assert passes < 1.8e9, passes
    assert passes < 2 * plan["hbm_bytes_fwd"] + plan["hbm_bytes_bwd"] \
        < 3.1e9, plan
    alone = sum(r + w for op, _, r, w, _ in ops if op in (
        "copy", "slice", "broadcast", "pad", "concatenate", "convert",
        "transpose"))
    assert passes + alone < 2.5e9 < 0.6 * 12.76e9, (passes, alone)


def test_latent_block_keeps_its_scopes_through_the_chips_compiler(
        one_chip, on_chip_branch):
    """The same compiled block, read as a chip trace's labels are
    (``benchmark/op_scopes.py``; an instruction's ``op_name`` is the
    ``tf_op`` of its events): the TPU compiler's fusion and layout passes
    leave the scope ``attention`` on every fusion that holds a convolution
    and on every Mosaic call, each call in the kernel-call scope of the
    plan it took, and forward, replay and backward are all there. What
    carries no ``op_name`` at all is the compiler's own (copies, bitcasts,
    tuple plumbing): its share of the entry's instructions is printed."""
    import re

    from benchmark import op_scopes

    comps = _computations(_glm_attention_block(one_chip)[3])
    entry = [ln for ln in comps["ENTRY"] if " = " in ln]
    matmuls, calls, passes, bare = 0, {}, set(), 0
    for ln in entry:
        name = re.search(r'op_name="([^"]*)"', ln)
        parts = op_scopes.elements(name.group(1) if name else "")
        bare += name is None
        body = re.search(r" fusion\(.*calls=%?([\w.\-]+)", ln)
        if body and any(" convolution(" in b for b in comps[body.group(1)]):
            matmuls += 1
            assert op_scopes.bucket(parts) == "attention", ln[:300]
            passes.add(op_scopes.which_pass(parts))
        if 'custom_call_target="tpu_custom_call"' in ln:
            assert op_scopes.bucket(parts) == "attention", ln[:300]
            calls[op_scopes.kernel_scope(parts)] = \
                calls.get(op_scopes.kernel_scope(parts), 0) + 1
    assert matmuls >= 20 and passes == {"forward", "replay", "backward"}, (
        matmuls, passes)
    # the replay makes the forward call again here: nothing of this
    # block's checkpoint keeps ``o`` and ``lse`` by name
    assert calls == {"flash.fwd.stream": 2, "flash.dq.stream": 1,
                     "flash.dkdv.stream": 1}, calls
    print(f"latent block: {bare} of {len(entry)} entry instructions carry "
          f"no op_name ({100.0 * bare / len(entry):.1f}%)")


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


def _while_bodies(text):
    """The scheduled instructions of every ``while`` body of a compiled
    module: {computation name: [line, ...]}."""
    import re

    names = set(re.findall(r"body=%?([\w.\-]+)", text))
    bodies, cur = {}, None
    for ln in text.splitlines():
        m = re.match(r"^%?([\w.\-]+) \(.*\) -> .* \{$", ln)
        if m:
            cur = m.group(1) if m.group(1) in names else None
            if cur:
                bodies[cur] = []
        elif ln.startswith("}"):
            cur = None
        elif cur:
            bodies[cur].append(ln.strip())
    return bodies


COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def test_2b7_train_step_fits_one_chip(topo, on_chip_branch):
    compiled = _step_2b7(topo, 1)
    mem = compiled.memory_analysis()
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert need < V5E_HBM, mem
    # forward, dq, dkdv: the checkpoint keeps the kernel's output, so the
    # compiled backward holds no second forward call
    assert compiled.as_text().count("tpu_custom_call") == 3


def test_one_chip_step_has_no_collective(topo, on_chip_branch):
    """No tensor axis, no plan: the one-chip program talks to nobody."""
    text = _step_2b7(topo, 1).as_text()
    assert not [c for c in COLLECTIVES
                if c + "(" in text or c + "-start(" in text]


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


def _loop_permutes(text, rows_shape):
    """[(loop body, permute, matmul fusions between its start and its
    done)] for every collective-permute of ``rows_shape`` in the layer
    loops of a compiled step, having checked that the loops hold no
    blocking all-reduce of an activation and that nothing joins or splits
    the rows as an op of its own (a copy of every part)."""
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


# cell -> (config module of the benchmark, its function, family): the
# cells whose whole step is compiled here, from the cell's own files
_CELL_STEPS = {
    "train-glm52-ep32-s16384-b1": ("model_glm52", "latent_config", "latent"),
    "train-commandaplus-ep16-s8192-b1": ("model_commanda", "moe_config",
                                         "moe"),
    "train-granite4hs-ep8-s8192-b2": ("model_granite", "hybrid_config",
                                      "hybrid"),
    "train-mellum2-ep4-s16384-b1": ("model_mellum", "moe_config", "moe"),
    "train-nemotron3nano-ep8-s8192-b2": ("model_nemotron", "hybrid_config",
                                         "hybrid"),
    "train-minicpmsala-l4-s16384-b1": ("model_sala", "sala_config", "sala"),
    "train-lfm2-ep4-s16384-b1": ("model_lfm2", "hybrid_config", "hybrid"),
}


def _compile_cell_step(name, topo, monkeypatch):
    """A one-chip cell's train step by its recipe, compiled for a described
    v5e chip that states a v5e's limit, 16,909,336,064 (here no device
    states one):
    (compiled, plan bytes, the ``remat.plan`` instant's attributes)."""
    import importlib

    import jax
    import jax.numpy as jnp
    import optax

    from benchmark import resolve
    from ray_tpu.parallel import (MeshSpec, ShardingRules, build_mesh,
                                  train_step)
    from ray_tpu.util import tracing

    cell = resolve.cell(name)
    recipe, mix = cell["train"], cell["mix"]
    module, make, family = _CELL_STEPS[name]
    cfg = getattr(importlib.import_module(f"benchmark.{module}"), make)(
        cell["config"], **{k: recipe[k] for k in (
            "attn_impl", "gmm_impl", "ssd_impl", "remat", "f32_logits")
            if k in recipe})
    fam = importlib.import_module(f"ray_tpu.models.{family}")
    said = []
    instant = tracing.instant
    monkeypatch.setattr(train_step, "device_bytes_limit",
                        lambda mesh: 16_909_336_064)
    monkeypatch.setattr(tracing, "instant", lambda n, attrs=None, **kw: (
        said.append((n, attrs)), instant(n, attrs, **kw))[1])
    mesh = build_mesh(MeshSpec(**recipe["mesh"]), devices=topo.devices[:1])
    rules, opt = getattr(ShardingRules, recipe["rules"])(), optax.adafactor(
        recipe["lr"])
    init_fn, state_sh = train_step.make_train_state_init(
        lambda k: fam.init_params(k, cfg), opt, mesh, rules,
        fam.param_specs(cfg))
    state = _with_shardings(
        jax.eval_shape(init_fn, jax.random.PRNGKey(0)), state_sh)
    shape = {"tokens": jax.ShapeDtypeStruct(
        (mix["batch"], mix["seq"] + 1), jnp.int32)}
    batch = _with_shardings(shape,
                            train_step.batch_sharding(mesh, rules, shape))
    compiled = train_step.make_train_step(
        lambda p, b: fam.loss_fn(p, b, cfg, mesh=mesh, rules=rules), opt,
        mesh, rules, state_sh, batch_shapes=shape).lower(
            state, batch).compile()
    mem = compiled.memory_analysis()
    return compiled, int(
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes), [
            a for n, a in said if n == "remat.plan"]


def test_command_a_plus_step_keeps_its_names_and_fits(topo, on_chip_branch,
                                                      monkeypatch):
    """The Command A+ cell's step with the names its plan keeps (all five:
    q, k, v, the shared SwiGLU's gate and up; 2.45e9 bytes over four
    layers): the plan stays under 12.0e9 (9,282,964,480 with none kept;
    10,938,735,616 when this was written), XLA rematerializes nothing of
    its own, and no checkpoint body computes a shared product again (the
    parent's held 8: gate and up, a layer)."""
    compiled, plan, said = _compile_cell_step(
        "train-commandaplus-ep16-s8192-b1", topo, monkeypatch)
    assert [(p["kept"], p["kept_bytes"], p["why"]) for p in said] == [
        ("attn_q,attn_k,attn_v,shared_gate,shared_up", 2_449_473_536,
         "room")]
    assert 9.3e9 < plan <= 12.0e9, plan
    text = compiled.as_text()
    assert text.count(".remat") == 0
    assert text.count("tpu_custom_call") == 100
    replayed = [ln for ln in text.splitlines() if "rematted_computation/"
                "feed_forward/shared/dot_general" in ln]
    assert not replayed, replayed[:2]
    assert "checkpoint/feed_forward/shared/dot_general" in text


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


def test_mellum2_step_keeps_q_beside_k_and_v(topo, on_chip_branch,
                                             monkeypatch):
    """The Mellum2 cell's step with passes of 49,152 rows: the estimate
    (11.17e9) leaves room for q beside k and v (2.01e9 bytes over twelve
    layers; at 65,536 rows it kept k and v alone and planned
    12,806,373,888), the plan stays under 15.0e9 (14,549,401,600 when this
    was written; with q kept at the OLD pass it compiled to 15.10e9, PR
    43) and XLA rematerializes nothing of its own."""
    compiled, plan, said = _compile_cell_step(
        "train-mellum2-ep4-s16384-b1", topo, monkeypatch)
    assert [(p["kept"], p["kept_bytes"], p["why"]) for p in said] == [
        ("attn_q,attn_k,attn_v", 2_013_265_920, "room")]
    assert 11.0e9 < plan <= 15.0e9, plan
    assert compiled.as_text().count(".remat") == 0


def test_nemotron_step_plans_under_the_figure_its_file_states(
        topo, on_chip_branch, monkeypatch):
    """The Nemotron 3 Nano cell's step (20 one-half blocks, each a run of
    its own: MEMEM*EMEMEM*EMEMEM*) keeps by the run: q, k and v in the three
    attention blocks, the shared expert's up product in all eight expert
    blocks and the in-projection's product in all nine mixers (4.46e9
    bytes, each charged 1.0 a byte in its run of one layer: the estimate
    reads 9.41e9 and the sum 13.88e9 of the 14.37e9 the rule leaves; at 1.5
    a byte a sixth product did not fit). The plan stays under 15.2e9
    (13,522,487,808 when this was written; 12,545,731,072 with five
    products kept, 9,834,501,632 with q, k and v alone, 9,556,182,016 with
    nothing; the configuration's file states 10.7e9 of PR 48's), XLA
    rematerializes nothing of its own, and no checkpoint body computes a
    kept product again: the shared expert's in no expert block, the
    in-projection's in no mixer's."""
    compiled, plan, said = _compile_cell_step(
        "train-nemotron3nano-ep8-s8192-b2", topo, monkeypatch)
    runs = {"M": "mix_proj", "E": "shared_up", "*": "attn_q+attn_k+attn_v"}
    kept = 16384 * 2 * (3 * 36 * 128 + 8 * 3712 + 9 * 10304)
    assert [(p["kept"], p["by_run"], p["kept_bytes"], p["charged"],
             p["why"]) for p in said] == [
        ("attn_q,attn_k,attn_v,shared_up,mix_proj",
         ",".join(runs[c] for c in "MEMEM*EMEMEM*EMEMEM*"), kept, kept,
         "room")]
    assert said[0]["runs"] == ("attn_q x3, attn_k x3, attn_v x3, "
                               "shared_up x8, mix_proj x9")
    assert said[0]["estimate"] + kept <= said[0]["ceiling"]
    assert 12.5e9 < plan < 15.2e9, plan
    text = compiled.as_text()
    assert text.count(".remat") == 0
    # a mixer block's scan forward, again under the checkpoint, backward;
    # an attention block's three flash calls; the grouped matmuls
    assert text.count("tpu_custom_call") >= 100
    lines = text.splitlines()
    replayed = [ln for ln in lines if "rematted_computation/"
                "feed_forward/shared/dot_general" in ln]
    assert not replayed, replayed[:2]
    assert "checkpoint/feed_forward/shared/dot_general" in text
    # (PR 48's program held nine: one a mixer's body; PR 53's four)
    made = [ln for ln in lines if "/mixer/dot_general" in ln
            and " convolution(" in ln and "= bf16[2,8192,10304]" in ln]
    assert len(made) == 9, len(made)
    again = [ln for ln in made if "rematted_computation/" in ln]
    assert not again, again[:2]


def test_lfm2_step_keeps_fourteen_in_projections_and_fits(
        topo, on_chip_branch, monkeypatch):
    """The LFM2 cell's step (24 layers, each a run of its own:
    DD*ccc*ccc*ccc*ccc*cc*cc, D a convolution layer with the dense SwiGLU)
    keeps gate, up and the in-projection's product in both dense layers, q,
    k and v in all six attention layers and the in-projection's product in
    the first twelve of the sixteen other convolution layers (4.36e9 bytes
    at 1.0 a byte: the estimate reads 9.91e9, the sum 14.27e9 of the
    14.37e9 the rule leaves, and a fifteenth product would pass it; at 1.5
    a byte the plan kept seven and was 13,349,467,136). The plan stays
    under 15.2e9 (14,756,315,136 when this was written: 0.998 bytes more a
    byte more kept), XLA rematerializes nothing of its own, and a
    checkpoint body computes the in-projection's product again in the last
    four convolution layers only, a dense layer's gate and up in none."""
    compiled, plan, said = _compile_cell_step(
        "train-lfm2-ep4-s16384-b1", topo, monkeypatch)
    runs = {"D": "ffn_gate+ffn_up+mix_proj", "c": "mix_proj", "-": "-",
            "*": "attn_q+attn_k+attn_v"}
    kept = 16384 * 2 * (2 * 2 * 7168 + 6 * 48 * 64 + 14 * 6144)
    assert [(p["kept"], p["by_run"], p["kept_bytes"], p["charged"],
             p["why"]) for p in said] == [
        ("attn_q,attn_k,attn_v,ffn_gate,ffn_up,mix_proj",
         ",".join(runs[c] for c in "DD*ccc*ccc*ccc*ccc*--*--"), kept, kept,
         "room")]
    assert said[0]["runs"] == ("attn_q x6, attn_k x6, attn_v x6, "
                               "ffn_gate x2, ffn_up x2, mix_proj x14")
    assert said[0]["estimate"] + kept <= said[0]["ceiling"]
    assert 13.4e9 < plan < 15.2e9, plan
    text = compiled.as_text()
    assert text.count(".remat") == 0
    assert text.count("tpu_custom_call") >= 500     # 502: PR 54's program
    lines = text.splitlines()
    made = [ln for ln in lines
            if "/mixer/short_conv/in_proj/dot_general" in ln
            and " convolution(" in ln and "= bf16[16384,6144]" in ln]
    again = [ln for ln in made if "rematted_computation/" in ln]
    assert (len(made), len(again)) == (18 + 4, 4), (len(made), len(again))
    replayed = [ln for ln in lines if "rematted_computation/"
                "feed_forward/dense/dot_general" in ln]
    assert not replayed, replayed[:2]


# --- GLM-5.2: attention over a learned set (ops/sparse_attention.py) -------
GLM52_ATTENTION = (1, 16384, 32, 256)      # the cell's B, S, heads held, D


def test_sparse_attention_calls_compile_at_the_glm52_cells_shape(
        one_chip, on_chip_branch):
    """The four Mosaic calls of the attention over a set (forward, the
    head-mean probabilities, dQ, dK/dV) lower for a v5e at the cell's
    shape, sets of 2,048 as a 0/1 int8 square, within the 16 MiB a call
    gets that asks for no more; the plans' fields are what the trace and
    the roofline reader go by."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import sparse_attention as sa

    B, S, H, D = GLM52_ATTENTION
    q = _sds((B, S, H, D), jnp.bfloat16, one_chip)
    keep = _sds((B, S, S), jnp.int8, one_chip)

    def loss(q, k, v, keep):
        o, p = sa.sparse_attention(q, k, v, keep, with_probs=True)
        return o.astype(jnp.float32).sum(), p

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                      has_aux=True)).lower(
        q, q, q, keep).compile().as_text()
    assert text.count("tpu_custom_call") == 4, text[:2000]
    for scope in ("sparse.fwd.mask", "sparse.probs.mask", "sparse.dq.mask",
                  "sparse.dkdv.mask"):
        assert scope in text, scope
    for call in ("fwd", "probs", "dq", "dkdv"):
        plan = sa.plan(B=B, H=H, S=S, T=S, D=D, dtype=jnp.bfloat16,
                       call=call)
        assert {"path", "call", "block_q", "block_k", "span", "in_flight",
                "vmem_bytes", "grid_steps", "live_steps"} <= set(plan)
        assert plan["span"] > 1, plan     # a span of blocks a grid step
        assert plan["vmem_bytes"] <= 16 * 2 ** 20, plan


def test_index_score_blocks_and_the_selection_fit_at_the_cells_shape(
        one_chip, no_persistent_cache):
    """One block of the indexer's scores and its exact selection at the
    cell's shape (2,048 queries' 32 heads of 128 over all 16,384 keys):
    the per-head products of 256 queries are alive at once, not the
    block's (4.3e9 bytes), and no sort is in the program."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import latent

    rows, S, IH, ID, topk = 2048, 16384, 32, 128, 2048

    def block(qI, w, kI):
        return latent.select(latent.index_scores(qI, w, kI), S - rows,
                             topk)

    compiled = jax.jit(block).lower(
        _sds((rows, IH, ID), jnp.bfloat16, one_chip),
        _sds((rows, IH), jnp.float32, one_chip),
        _sds((S, ID), jnp.bfloat16, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1.5e9, mem.temp_size_in_bytes
    text = compiled.as_text()
    assert " sort(" not in text and "topk" not in text.lower()


def test_glm52_step_fits_with_its_set_kept_and_selects_once(
        topo, on_chip_branch, monkeypatch):
    """The GLM-5.2 cell's step at 32 heads held, a stack a layer: the plan
    stays under 13.73e9 bytes (13,246,264,320 when this was written,
    13,276,408,320 before LI's gradient was made in the forward and
    14,093,904,384 with it made there a block wherever the scheduler
    liked: ``latent._index_loss_fwd``'s barriers; whole runs as stacks
    planned 17,861,688,832, over the chip) and XLA
    rematerializes nothing of its own; the layer checkpoint keeps each
    full layer's set so that no replay selects again (the selection's
    counting loop is in the program twice, once a full layer, not four
    times) and LI's gradients, so that the head-mean probabilities are
    computed twice a step, once a full layer, and the backward scan holds
    of LI its gradients' scaling and no loop or product; every kind of
    Mosaic call is there."""
    compiled, plan, said = _compile_cell_step(
        "train-glm52-ep32-s16384-b1", topo, monkeypatch)
    assert [(p["kept"], p["why"]) for p in said] == [("", "no room")]
    assert plan <= 13.73e9, plan
    text = compiled.as_text()
    assert text.count(".remat") == 0
    # (LI is computed a sequence at a time: its scope stands under vmap)
    for scope in ("sparse.fwd.mask", "sparse.probs.mask", "sparse.dq.mask",
                  "sparse.dkdv.mask", "attention/indexer",
                  "attention/select", "attention/vmap(index_loss)"):
        assert scope in text, scope
    lines = text.splitlines()
    replayed = [ln for ln in lines
                if "rematted_computation/attention/select" in ln
                and "while" in ln]
    assert not replayed, replayed[:2]
    calls = {scope: sum("tpu_custom_call" in ln and scope in ln
                        for ln in lines)
             for scope in ("sparse.fwd.mask", "sparse.probs.mask")}
    assert calls == {"sparse.fwd.mask": 5, "sparse.probs.mask": 2}, calls
    late = [ln for ln in lines if "transpose(jvp(layers))" in ln
            and "vmap(index_loss)" in ln]
    assert late and not [ln for ln in late if " while(" in ln
                         or "cjd,td->cjt" in ln
                         or "rematted_computation/attention/vmap" in ln], \
        late[:2]


# --- MiniCPM-SALA: attention over a set of blocks, the wide scan ------------
SALA_ATTENTION = (1, 16384, 32, 2, 128)    # the cell's B, S, H, KV, D


def test_block_set_calls_compile_at_the_sala_cells_shape(one_chip,
                                                         on_chip_branch):
    """The three Mosaic calls of attention over a set of blocks a KV group
    (forward, dQ, dK/dV with the group's 16 heads innermost) lower for a
    v5e at the cell's shape, the set [1, 2, 16384, 256] int8, within the
    16 MiB a call gets that asks for no more."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import sparse_attention as sa

    B, S, H, KV, D = SALA_ATTENTION
    q = _sds((B, S, H, D), jnp.bfloat16, one_chip)
    k = _sds((B, S, KV, D), jnp.bfloat16, one_chip)
    sel = _sds((B, KV, S, S // sa.SET_BLOCK), jnp.int8, one_chip)

    def loss(q, k, v, sel):
        return sa.block_sparse_attention(q, k, v, sel).astype(
            jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, k, k, sel).compile().as_text()
    assert text.count("tpu_custom_call") == 3, text[:2000]
    for scope in ("sparse.fwd.blocks", "sparse.dq.blocks",
                  "sparse.dkdv.blocks"):
        assert scope in text, scope
    for call in ("fwd", "dq", "dkdv"):
        plan = sa.plan(B=B, H=H, S=S, T=S, D=D, dtype=jnp.bfloat16,
                       call=call, blocks=S // sa.SET_BLOCK, group=H // KV)
        assert plan["path"] == "blocks" and plan["span"] > 1, plan
        assert plan["vmem_bytes"] <= 16 * 2 ** 20, plan


@pytest.mark.parametrize("chunk", [128, 256])
def test_ssd_scan_compiles_at_lightning_widths(chunk, one_chip,
                                               on_chip_branch):
    """The wide scan (32 heads of 128 with keys of their own, a constant
    decay a head, the rates in SMEM) lowers for a v5e at the cell's shape:
    two Mosaic calls, no [B, S, H] array of steps among their operands."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import ssd

    B, S, H, _, P = SALA_ATTENTION
    x = _sds((B, S, H, P), jnp.bfloat16, one_chip)
    a = _sds((H,), jnp.float32, one_chip)

    def loss(x, bm, cm, a):
        return ssd.ssd_scan(x, None, a, bm, cm, chunk=chunk,
                            impl="pallas").astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x, a).compile().as_text()
    assert text.count("tpu_custom_call") == 2, text[:2000]
    assert f"f32[{B},{S},{H}]" not in text
    plan = ssd.plan(S=S, H=H, P=P, N=P, chunk=chunk, dtype=jnp.bfloat16,
                    impl="pallas", G=H, steady=True)
    assert plan["layout"] == "wide" and plan["heads_per_block"] == 4, plan
    assert plan["vmem_bytes"] <= 16 * 2 ** 20, plan


def test_sala_step_fits_with_its_set_kept_and_selects_once(
        topo, on_chip_branch, monkeypatch):
    """The MiniCPM-SALA cell's step (one sparse layer, three lightning
    layers, the whole vocabulary): the sparse layer, a run of its own,
    keeps its SwiGLU's gate and up (1.07e9 bytes: the estimate reads
    12.39e9 of the 14.37e9 the rule leaves; the lightning layers' stack of
    three would need 2.42e9 a name), the plan stays under that ceiling
    (13,769,958,400 when this was written, 12,696,442,368 with nothing
    kept), XLA rematerializes nothing of its own, the layer
    checkpoint keeps the sparse layer's set so that the replay selects
    nothing (the selection's top-k is in the program once), and every kind
    of Mosaic call is there."""
    compiled, plan, said = _compile_cell_step(
        "train-minicpmsala-l4-s16384-b1", topo, monkeypatch)
    assert [(p["kept"], p["by_run"], p["kept_bytes"], p["why"])
            for p in said] == [
        ("ffn_gate,ffn_up", "ffn_gate+ffn_up,-", 2 * 16384 * 16384 * 2,
         "room")]
    assert 12.7e9 < plan < 14.37e9, plan
    text = compiled.as_text()
    assert text.count(".remat") == 0
    for scope in ("sparse.fwd.blocks", "sparse.dq.blocks",
                  "sparse.dkdv.blocks", "ssd.fwd.pallas", "ssd.bwd.pallas",
                  "attention/sparse/block_select",
                  "attention/lightning/scan"):
        assert scope in text, scope
    lines = text.splitlines()
    replayed = [ln for ln in lines
                if "rematted_computation/attention/sparse/block_select" in ln]
    assert not replayed, replayed[:2]
    calls = {scope: sum("tpu_custom_call" in ln and scope in ln
                        for ln in lines)
             for scope in ("sparse.fwd.blocks", "ssd.fwd.pallas",
                           "ssd.bwd.pallas")}
    # the sparse forward once (o and lse are kept); the scan's forward in
    # the forward and again in the replay, one body a run of three layers
    assert calls == {"sparse.fwd.blocks": 1, "ssd.fwd.pallas": 2,
                     "ssd.bwd.pallas": 1}, calls
