"""Ask the TPU's compiler, without a chip: LFM2.

The cell's whole step (``models/hybrid.py``: short convolutions, leading
dense layers, heads of 64), one gate-taps-gate pass and its flash calls at
half a lane tile. A file of its own: the longest compile of the suite.

Compiles against a described (device-less) v5e; ``tests/described_tpu.py``
has the fixtures, the helpers and the rule that put each case where it is.
"""

from described_tpu import _compile_cell_step, _sds


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
