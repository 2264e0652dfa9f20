"""Ask the TPU's compiler, without a chip: Nemotron 3 Nano.

The cell's whole step (``models/hybrid.py``: blocks of one half, every block a
run of its own) and its scan in groups. A file of its own: beside another
whole step it would pass the seconds a file should hold.

Compiles against a described (device-less) v5e; ``tests/described_tpu.py``
has the fixtures, the helpers and the rule that put each case where it is.
"""

from described_tpu import _compile_cell_step, _sds


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
    # (under the scope ``in_proj`` since PR 63 named the mixer's parts)
    made = [ln for ln in lines if "/mixer/in_proj/dot_general" in ln
            and " convolution(" in ln and "= bf16[2,8192,10304]" in ln]
    assert len(made) == 9, len(made)
    again = [ln for ln in made if "rematted_computation/" in ln]
    assert not again, again[:2]


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
