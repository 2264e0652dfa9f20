"""Times the delta rule's two Mosaic calls alone, form by form, at the Ling
cell's shape ([1, 16384, 32, 128] bf16; PERF.md 6, PR 59), and reads each
form against the op's plain path in float32 at a small shape:

    python tests/delta_rule_forms.py [--parent DIR] [--only WORDS] [--tiny]
    python tests/delta_rule_forms.py --cut [--ling] [--parent DIR] ...

A form is the module as it is at some chunk, or with one of its functions
replaced while the calls trace (an ablation: what a part costs is the time
that goes when it does nothing). ``--parent DIR`` times the calls of an
unpacked parent commit beside them, as they are and with the inverse, the
pair blocks and the running sum taken out in turn. One JSON line a form;
on the chip through the chip tool, ``--tiny`` rehearses on the CPU.

``--cut`` (PERF.md 6, PR 66) times the cut for a gate with NO bound
(``lower_bound`` None) at the Solar cell's shape ([1, 16384, 64, 128] bf16,
a softplus gate that leaves -5): as it is, with the pair blocks and the pair
gradients taken out in turn, the bounded cut over the same inputs, and
every form of a level in ``tests/delta_rule_cut_forms.py`` (add a line to
its ``_forms`` to time another); ``--ling`` the same at the Ling cell's
shape over its bounded inputs. Every array is an argument of the timed
program (a constant folded into it is not what a step pays).
"""

import argparse
import functools
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops import delta_rule  # noqa: E402


def inputs(key, B, S, H, d, dtype, bounded=True):
    """``bounded``: a gate in (-5, 0) and beta in (0, 1), Ling's; else
    Solar's, a softplus gate that leaves -5 on a third of its steps (down
    to about -60) and beta in (0, 2)."""
    ks = jax.random.split(key, 7)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    q = unit(jax.random.normal(ks[0], (B, S, H, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (B, S, H, d)))
    v = jax.random.normal(ks[2], (B, S, H, d))
    draw = jax.random.normal(ks[3], (B, S, H, d))
    g = -5.0 * jax.nn.sigmoid(2 * draw) if bounded \
        else -4.0 * jax.nn.softplus(3 * draw)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H))) \
        * (1.0 if bounded else 2.0)
    do = jax.random.normal(ks[5], (B, S, H, d))
    return tuple(a.astype(dtype) for a in (q, k, v)) + (g, beta), \
        do.astype(dtype)


def timed(fn, *args, runs=5):
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(runs):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / runs * 1e3, out


def the_calls(mod, chunk, shape, dtype, bounded=True):
    """(forward, backward) of ``mod``'s two calls, jitted, on the flat
    arrays ``gated_delta_rule`` hands them; ``bounded`` False: the cut
    that needs no bound on the gate (``lower_bound`` None)."""
    B, S, H, d = shape
    said = mod.plan(B=B, S=S, H=H, dk=d, dv=d, chunk=chunk, dtype=dtype,
                    impl="pallas",
                    **({} if bounded else {"lower_bound": None}))
    kw = dict(chunk=said["chunk"], heads=said["heads_per_block"], dk=d, dv=d,
              clamp=40.0 if bounded else None)
    if "inverse_side" in said:
        kw["per"] = said["inverse_side"] // said["chunk"]
    return (jax.jit(functools.partial(mod._forward_call, **kw)),
            jax.jit(functools.partial(mod._backward_call, **kw)), said)


def flat_inputs(args, do, heads):
    q, k, v, g, beta = args
    B, S, H, d = q.shape
    flat = lambda a: a.reshape(B, S, -1)                       # noqa: E731
    by_block = beta.reshape(B, S, H // heads, heads).transpose(0, 2, 1, 3)
    return (flat(q), flat(k), flat(v), flat(g), by_block,
            jnp.zeros((B, H * d, d), jnp.float32)), flat(do)


def time_form(name, mod, chunk, patches, args, do, backward=True,
              bounded=True):
    own = {n: getattr(mod, n) for n in patches}
    for n, fn in patches.items():
        setattr(mod, n, fn)
    if hasattr(mod, "_products"):
        mod._products.cache_clear()
    jax.clear_caches()
    try:
        fwd, bwd, said = the_calls(mod, chunk, args[0].shape, args[0].dtype,
                                   bounded)
        flat, flat_do = flat_inputs(args, do, said["heads_per_block"])
        ms_f, (_, states) = timed(fwd, *flat)
        line = {"form": name, "chunk": said["chunk"],
                "heads_per_block": said["heads_per_block"],
                "inverse_side": said.get("inverse_side"),
                "f32_products": [said.get("f32_products_fwd"),
                                 said.get("f32_products_bwd")],
                "mxu_passes": [said.get("mxu_passes_fwd"),
                               said.get("mxu_passes_bwd")],
                "mxu_rows": [said.get("mxu_rows_fwd"),
                             said.get("mxu_rows_bwd")],
                "cut": said.get("cut"),
                "fwd_ms": round(ms_f, 3)}
        if backward:
            ms_b, _ = timed(bwd, *flat[:5], states, flat_do)
            line["bwd_ms"] = round(ms_b, 3)
            line["layer_step_ms"] = round(2 * ms_f + ms_b, 3)
    except Exception as e:                                     # noqa: BLE001
        line = {"form": name, "error": f"{type(e).__name__}: {str(e)[:600]}"}
    finally:
        for n, fn in own.items():
            setattr(mod, n, fn)
    return line


def float32_reading(mod, chunk, shape, seed):
    """Relative L2 of o and the five gradients, the calls on float32 inputs
    against the plain path in float32 at ``highest``."""
    args, do = inputs(jax.random.PRNGKey(seed), *shape, jnp.float32)

    def parts(impl, chunk):
        def run(*a):
            o, back = jax.vjp(lambda *b: mod.gated_delta_rule(
                *b, chunk=chunk, impl=impl), *a)
            return (o, *back(do))
        return jax.jit(run)

    with jax.default_matmul_precision("highest"):
        want = parts("xla", 64)(*args)
    got = parts("pallas", chunk)(*args)
    return {n: float(jnp.linalg.norm((a - b).ravel())
                     / jnp.linalg.norm(b.ravel()))
            for n, a, b in zip("o dq dk dv dg dbeta".split(), got, want)}


def three_parts(x):
    """x float32 -> (hi, mid, lo) bfloat16 with hi + mid + lo == x: each
    part takes the next eight bits of the mantissa, and each remainder is
    exact in float32."""
    bf, f32 = jnp.bfloat16, jnp.float32
    hi = x.astype(bf)
    rest = x - hi.astype(f32)
    mid = rest.astype(bf)
    return hi, mid, (rest - mid.astype(f32)).astype(bf)


def _triangle(c, from_end):
    t, s = delta_rule._rows_cols(c)
    return jnp.where((s >= t) if from_end else (t >= s), 1.0, 0.0)


def six_pass_sum(x, from_end=False):
    """The running sum as the parent made it: a float32 product with the
    0/1 triangle at ``HIGHEST``."""
    return delta_rule._dot32(_triangle(x.shape[0], from_end), x)


def three_pass_sum(x, from_end=False):
    """The same sum by three passes: the triangle is exact in bfloat16, so
    of the six passes the three that take its middle and low part multiply
    by zero; one pass for each part of x, summed from the smallest."""
    tri = _triangle(x.shape[0], from_end).astype(jnp.bfloat16)
    delta_rule._count(0, 3, tri, delta_rule._NN)
    hi, mid, lo = (jax.lax.dot_general(tri, part, delta_rule._NN,
                                       preferred_element_type=jnp.float32)
                   for part in three_parts(x))
    return lo + mid + hi


def stacked_dot32(a, b, dims=delta_rule._NN):
    """The six passes of a float32 product with each part of b met once:
    a's parts stacked along its free dimension (three products of 3, 2
    and 1 parts' rows for six of one), summed in float32."""
    delta_rule._count(1, 6, a, dims)
    free = 1 - dims[0][0][0]
    n = a.shape[free]
    a_parts, b_parts = (three_parts(x.astype(jnp.float32))
                        for x in (a, b))
    total = None
    for take, part in zip((1, 2, 3), reversed(b_parts)):       # lo first
        out = jax.lax.dot_general(
            jnp.concatenate(a_parts[:take], axis=free), part, dims,
            preferred_element_type=jnp.float32)
        for i in reversed(range(take)):
            piece = out[i * n:(i + 1) * n]
            total = piece if total is None else total + piece
    return total


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default="chiprun_out/delta_rule_forms.jsonl")
    ap.add_argument("--only", help="forms whose name holds this, no other")
    ap.add_argument("--cut", action="store_true",
                    help="the cut for a gate with no bound (lower_bound "
                    "None) at the Solar cell's shape, 64 heads, its parts "
                    "taken out and its forms in turn (PR 66)")
    ap.add_argument("--ling", action="store_true",
                    help="with --cut: at the Ling cell's shape, 32 heads, "
                    "over its bounded inputs")
    opts = ap.parse_args()
    heads = 64 if opts.cut and not opts.ling else 32
    shape = (1, 512, 2, 16) if opts.tiny else (1, 16384, heads, 128)
    small = (1, 256, 2, 16) if opts.tiny else (1, 2048, 4, 128)
    args, do = inputs(jax.random.PRNGKey(59), *shape, jnp.bfloat16,
                      bounded=opts.ling or not opts.cut)
    os.makedirs(os.path.dirname(opts.out) or ".", exist_ok=True)
    out = open(opts.out, "w")

    def say(line):
        print(json.dumps(line), flush=True)
        out.write(json.dumps(line) + "\n")
        out.flush()

    def run(name, mod, chunk, patches, backward=True, bounded=True):
        if not opts.only or opts.only in name:
            say(time_form(name, mod, chunk, patches, args, do, backward,
                          bounded))

    say({"device": jax.devices()[0].device_kind, "shape": shape})
    eye = lambda a, block=None: jnp.where(                     # noqa: E731
        jnp.equal(*delta_rule._rows_cols(a.shape[0])), 1.0, 0.0)
    none = lambda q, k, cum, sub, clamp: (                     # noqa: E731
        jnp.zeros((k.shape[0],) * 2), jnp.zeros((k.shape[0],) * 2))
    same = lambda g, from_end=False: g                         # noqa: E731

    def parts_out(mod, tag):
        """The inverse, the pair blocks and the running sum taken out of
        ``mod``'s calls in turn."""
        gone = {"_unit_lower_inverse": eye}
        run(f"{tag}, the inverse the identity", mod, 64, dict(gone))
        gone["_pair_blocks"] = none
        run(f"{tag}, and the pair blocks zeros", mod, 64, dict(gone),
            backward=False)
        gone["_running_sum"] = same
        run(f"{tag}, and the running sum the identity", mod, 64, dict(gone),
            backward=False)

    parent = None
    if opts.parent:
        spec = importlib.util.spec_from_file_location(
            "parent_delta_rule",
            os.path.join(opts.parent, "ray_tpu/ops/delta_rule.py"))
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
    if opts.cut:
        return the_cut(run, parent)
    if parent:
        run("parent as it is", parent, 64, {})
        parts_out(parent, "parent")
        run("parent at a chunk of 128", parent, 128, {})
    never = 1 << 20
    run("as it is (chunk 64)", delta_rule, 64, {})
    parts_out(delta_rule, "chunk 64")
    for shifted, half in ((1, never), (2, never), (1, 8), (2, 8), (4, 16),
                          (4, 8), (8, 8)):
        run(f"chunk 64, rounds from blocks of up to {shifted} by shifted "
            f"multiply-adds, the odd blocks' rows alone from blocks of "
            f"{half if half < never else 'no size'}", delta_rule, 64,
            {"SHIFTED_BLOCKS": shifted, "HALF_ROWS": half})
    for chunk, heads in ((64, 4), (64, 8), (128, 2), (128, 4), (32, 4),
                         (32, 8)):
        run(f"chunk {chunk}, {heads} heads a block", delta_rule, chunk,
            {"HEADS_PER_BLOCK": heads})
    for chunk in (64, 128):
        run(f"chunk {chunk}, the sums by three passes", delta_rule, chunk,
            {"_running_sum": three_pass_sum})
        run(f"chunk {chunk}, the sums by six passes", delta_rule, chunk,
            {"_running_sum": six_pass_sum})
    run("chunk 64, float32 products by stacked parts", delta_rule, 64,
        {"_dot32": stacked_dot32})
    run("chunk 64, a head an inverse (side 64)", delta_rule, 64,
        {"_SIDE": 64})
    for chunk in () if opts.only else (64, 128):
        for seed in (1, 2):
            say({"float32_reading": float32_reading(delta_rule, chunk, small,
                                                    seed),
                 "chunk": chunk, "seed": seed, "shape": small})


def the_cut(run, parent):
    """The cut in halves alone (PERF.md 6, PR 66): what it costs (the pair
    blocks and the pair gradients taken out in turn), the bounded cut over
    the same inputs, and every form of a level that was tried."""
    import delta_rule_cut_forms as cut

    free = functools.partial(run, bounded=False)
    no_blocks = lambda q, k, *_: (                             # noqa: E731
        jnp.zeros((k.shape[0],) * 2), jnp.zeros((k.shape[0],) * 2))
    no_grads = lambda q, k, *_: (jnp.zeros_like(k),) * 3       # noqa: E731
    for tag, mod in (("parent, ", parent), ("", delta_rule)):
        if mod is None:
            continue
        free(f"{tag}no bound, as it is", mod, 64, {})
        free(f"{tag}no bound, the pair blocks zeros", mod, 64,
             {"_pair_blocks_free": no_blocks})
        free(f"{tag}no bound, the pair gradients zeros", mod, 64,
             {"_pair_grads_free": no_grads})
        run(f"{tag}the bounded cut on the same inputs", mod, 64, {})
    for name, forward, back in cut.FORMS:
        patches = {"_pair_blocks_free": forward}
        if back:
            patches["_pair_grads_free"] = back
        free(f"no bound, {name}", delta_rule, 64, patches)


if __name__ == "__main__":
    main()
