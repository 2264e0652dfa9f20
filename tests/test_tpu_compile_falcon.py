"""Ask the TPU's compiler, without a chip: Falcon-H1.

The cell's whole step (``models/falcon.py``: a block of two first halves,
the scan's tile layout, the widest SwiGLU any cell has) and the scan's two
calls alone at the cell's shape. A file of its own: one whole-step compile.

Compiles against a described (device-less) v5e; ``tests/described_tpu.py``
has the fixtures, the helpers and the rule that put each case where it is.
"""

from described_tpu import _compile_cell_step, _sds

FALCON_SCAN = (1, 16384, 32, 128, 256, 2, 128)    # B, S, H, P, N, G, chunk


def test_falcon_step_fits_with_both_halves_in_every_block(
        topo, on_chip_branch, monkeypatch):
    """The Falcon-H1 cell's step (four blocks, a stack a layer): the plan
    stays under 15.8e9 bytes and over a quarter of the chip, XLA
    rematerializes nothing of its own, every block holds flash's three
    calls and the scan's two (the forward again in the replay), and the
    block's norm and sum stand under their own scope."""
    compiled, plan, said = _compile_cell_step(
        "train-falconh1-l4-s16384-b1", topo, monkeypatch)
    # the estimate reads 10.97e9 (a block of two first halves stands at
    # the larger of its halves' and its SwiGLU's backward bytes; their sum
    # read 14.12e9 where the compiler plans 10.38e9 with nothing kept): q,
    # k, v and the SwiGLU's gate in every run, 3.29e9 of the 3.41e9 that
    # the rule's 14.37e9 leaves; up (0.70e9 a run) finds no room
    run = "attn_q+attn_k+attn_v+ffn_gate"
    assert [(p["by_run"], p["kept_bytes"], p["estimate"], p["why"])
            for p in said] == [
        (",".join([run] * 4), 4 * 16384 * 2 * (28 * 128 + 21504),
         10_967_492_248, "room")], said
    # 13,250,576,384 when this was written: 78.4% of the chip, 0.87 bytes
    # more a byte kept (10,615,357,440 with q in two runs and k in four)
    assert 0.25 * 16_909_336_064 < 12.9e9 < plan < 13.6e9, plan
    text = compiled.as_text()
    assert text.count(".remat") == 0
    for scope in ("ssd.fwd.pallas", "ssd.bwd.pallas", "closed_call/block/",
                  "closed_call/attention/", "closed_call/mixer/scan/",
                  "closed_call/mixer/gated_norm/", "closed_call/mixer/conv/",
                  "closed_call/feed_forward/"):
        assert scope in text, scope
    lines = text.splitlines()
    calls = {scope: sum("tpu_custom_call" in ln and scope in ln
                        for ln in lines)
             for scope in ("ssd.fwd.pallas", "ssd.bwd.pallas")}
    # four runs of one layer: the scan's forward in the forward and again
    # in the replay, its backward once
    assert calls == {"ssd.fwd.pallas": 8, "ssd.bwd.pallas": 4}, calls
    assert text.count("tpu_custom_call") == 4 * (3 + 3), \
        text.count("tpu_custom_call")


def test_ssd_scan_compiles_at_falcon_widths(one_chip, on_chip_branch):
    """The tile layout (32 heads of 128 with steps, a state of 256, two
    groups, chunks of 128) lowers for a v5e at the cell's shape: exactly
    two Mosaic calls with the operands ``falconh1_kernel_roofline`` tells
    them by, the chunks' incoming states the only state among the results,
    and the plan's count of an instance's VMEM under ``TILE_VMEM``."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import ssd

    B, S, H, P, N, G, chunk = FALCON_SCAN
    bf, f32 = jnp.bfloat16, jnp.float32
    args = (_sds((B, S, H, P), bf, one_chip), _sds((B, S, H), f32, one_chip),
            _sds((H,), f32, one_chip), _sds((B, S, G, N), bf, one_chip),
            _sds((B, S, G, N), bf, one_chip))

    def loss(*a):
        return ssd.ssd_scan(*a, chunk=chunk, impl="pallas").astype(f32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *args).compile().as_text()
    assert text.count("tpu_custom_call") == 2, text[:2000]
    assert f"f32[{B},{S // chunk},{H * P},{N}]" in text
    plan = ssd.plan(S=S, H=H, P=P, N=N, chunk=chunk, dtype=bf, impl="pallas",
                    G=G)
    assert (plan["layout"], plan["heads_per_block"]) == ("tile", 16), plan
    assert plan["vmem_bytes"] <= ssd.TILE_VMEM, plan
