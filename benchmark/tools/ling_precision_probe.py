"""What decides ``correct`` in a train_kda cell, read at the cell's real size on the
chip for the program as it is (every seed given) and, on the first seed, for wrong
programs: the KDA projections rounded to 8 bits (the nearest precision below the
configuration's bf16), the state carried in bfloat16 from chunk to chunk, the gate's
running sum in bfloat16, beta left out of the erase term (a gated linear attention),
one decay a head for 128 a head, the gate's softplus form without its bound, the top
8 taken over all 512 experts without the group limit, the bias left out of the
choice. The cases that change the recurrence run the op's plain path (``kda_impl``
"xla", forward only: no kernel takes a wrong recurrence), so that path is read as it
is too, for what the path alone moves. One process, no cluster; prints one JSON line
a case.

    chiprun --chips 1 -- python3 benchmark/tools/ling_precision_probe.py <cell> [--plain|--op] [seed ...]

``--plain``: the plain path's cases alone (as it is, the state and the gate's running
sum in bfloat16, beta left out), on the first seed. ``--op``: part (e) alone, the
delta rule's calls on the first layer's scan inputs at the cell's shape through the
kind's own ``op_agreement``, every seed given: the kernel pair in the timed type and
on the same values in float32, and on the float32 values the plain path with its
state or its gate's running sum rounded to bfloat16; each line says the largest of
the six parts beside the limit of the cell's ``check`` it is held to.
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import model_ling, resolve  # noqa: E402
from benchmark.kinds import train_kda as kind  # noqa: E402
from ray_tpu.models import ling  # noqa: E402
from ray_tpu.ops import delta_rule  # noqa: E402

cell = resolve.cell(sys.argv[1])
only_plain, only_op = "--plain" in sys.argv, "--op" in sys.argv
seeds = [int(s) for s in sys.argv[2:] if not s.startswith("--")] or [2147483659]
recipe, mix = cell["train"], cell["mix"]
sizes = model_ling.sizes(cell["config"])
cfg = model_ling.ling_config(cell["config"], **{k: recipe[k] for k in (
    "attn_impl", "gmm_impl", "kda_impl", "remat", "f32_logits") if k in recipe})
print("device", jax.devices()[0].device_kind, flush=True)
program, reference = kind.token_loss_fns(cfg, sizes)
plain = cfg.replace(kda_impl="xla")


def each(params, fn):
    return dict(params, layers=[fn(run) for run in params["layers"]])


# reduce_precision and not a cast there and back: on the TPU the compiler may drop
# such a pair of casts (PERF.md 6, PR 26). A leaf at a time: a wrong program's tree
# shares every leaf it does not change with the right one's
_rounded = jax.jit(lambda w: jax.lax.reduce_precision(w, exponent_bits=4,
                                                      mantissa_bits=3))
# a float32 array rounded to what bfloat16 holds, by reduce_precision too
_bf16 = lambda x: jax.lax.reduce_precision(x, exponent_bits=8,    # noqa: E731
                                           mantissa_bits=7)


def report(seed, name, fn, p, params, tokens):
    got, routes, _ = fn(p, tokens)
    ref, _, rec = reference(params, tokens, routes)
    print(json.dumps({"seed": seed, "case": name, **kind.loss_agreement(got, ref),
                      **kind.route_agreement(routes[0], rec, cfg.top_k),
                      "group_kept_ref": float(rec["group_kept"].mean())}),
          flush=True)


def with_chunk(chunk_fn):
    """The plain path's program with its chunk replaced; the caller puts
    the chunk back once the program has traced (at its first call)."""
    delta_rule._chunk_xla = chunk_fn(delta_rule._chunk_xla)
    return kind.token_loss_fns(plain, sizes)[0]


def bf16_state(own):
    def chunk(state, *a):
        state, o = own(state, *a)
        return _bf16(state), o
    return chunk


def bf16_gate_sum(own):
    def chunk(state, q, k, v, g, beta):
        # a gate whose running sum is the bfloat16 of the true one
        cum = _bf16(jnp.cumsum(g, axis=2))
        g = jnp.diff(cum, axis=2, prepend=jnp.zeros_like(cum[:, :, :1]))
        return own(state, q, k, v, g, beta)
    return chunk


def no_erase(own):
    def chunk(state, q, k, v, g, beta):
        # S <- Diag(exp g) S + beta k v^T: the same chunk with nothing erased
        c = q.shape[2]
        cum = jnp.cumsum(g, axis=2)
        seen = jnp.tril(jnp.ones((c, c), bool))
        decay = jnp.exp(jnp.where(
            seen[:, :, None], cum[:, :, :, None, :] - cum[:, :, None, :, :],
            -jnp.inf))
        aqk = jnp.einsum("bhtc,bhsc,bhtsc->bhts", q, k, decay)
        new = beta[..., None] * v
        o = jnp.einsum("bhtc,bhcv->bhtv", q * jnp.exp(cum), state) \
            + jnp.einsum("bhts,bhsv->bhtv", aqk, new)
        last = cum[:, :, -1:, :]
        state = jnp.exp(last)[:, :, 0, :, None] * state + jnp.einsum(
            "bhtc,bhtv->bhcv", k * jnp.exp(last - cum), new)
        return state, o
    return chunk


def op_cases(seed, params, tokens):
    """Part (e): the kind's own reading, for the calls as they are and for
    wrong recurrences on the plain path."""
    import time

    tol = recipe["check"]
    inputs = jax.jit(lambda p, t: kind.scan_inputs(cfg, p, t))(params, tokens)
    read = kind.op_agreement(
        inputs, jax.random.normal(jax.random.PRNGKey(seed % (2 ** 31)),
                                  inputs[2].shape, jnp.float32),
        cfg.kda_lower_bound)
    as_it_is = lambda *a: delta_rule.gated_delta_rule(         # noqa: E731
        *a, impl=cfg.kda_impl, lower_bound=cfg.kda_lower_bound)
    def on_plain(*a):
        with jax.default_matmul_precision("highest"):
            return delta_rule.gated_delta_rule(
                *a, impl="xla", lower_bound=cfg.kda_lower_bound)

    own_chunk = delta_rule._chunk_xla
    for name, scan, which, chunk in (
            ("the calls as they are", as_it_is, "timed", None),
            ("the calls as they are", as_it_is, "float32", None),
            ("the state in bfloat16", on_plain, "float32", bf16_state),
            ("the gate's running sum in bfloat16", on_plain, "float32",
             bf16_gate_sum)):
        if chunk is not None:       # in place while the case traces
            delta_rule._chunk_xla = chunk(own_chunk)
        t0 = time.perf_counter()
        got = read(scan, cfg.dtype if which == "timed" else jnp.float32)
        delta_rule._chunk_xla = own_chunk
        print(json.dumps({"seed": seed, "case": name, "inputs": which,
                          **got, "largest": max(got.values()),
                          "limit": tol.get("op_rel_" + which),
                          "seconds": round(time.perf_counter() - t0, 1)}),
              flush=True)


for seed in seeds:
    params = jax.jit(lambda k: kind.seeded_weights(k, cfg))(
        jax.random.PRNGKey(seed % (2 ** 31)))
    tokens = jax.random.randint(jax.random.PRNGKey((seed + 1) % (2 ** 31)),
                                (mix["batch"], mix["seq"] + 1), 0,
                                cfg.vocab_size, "int32")
    if only_op:
        op_cases(seed, params, tokens)
        continue
    if not only_plain:
        report(seed, "as it is", program, params, params, tokens)
    if seed != seeds[0]:
        continue
    zero = jax.jit(lambda w: w * 0)
    kda = ("wq", "wk", "wv", "w_decay", "wo")
    for name, p in {} if only_plain else {
            "8-bit KDA projections": each(params, lambda s: {
                k: (_rounded(w) if k in kda else w) for k, w in s.items()}
                if "w_decay" in s else s),
            "the bias left out of the choice": each(params, lambda s: {
                **s, "router_bias": zero(s["router_bias"])}
                if "router_bias" in s else s)}.items():
        report(seed, name, program, p, params, tokens)
        del p
    if not only_plain:
        report(seed, "the top 8 of all 512 without the group limit",
               kind.token_loss_fns(cfg.replace(n_group=1, topk_group=1),
                                   sizes)[0], params, params, tokens)
    report(seed, "the plain path as it is",
           kind.token_loss_fns(plain, sizes)[0], params, params, tokens)
    own_chunk = delta_rule._chunk_xla
    for name, wrong in (("the state in bfloat16", bf16_state),
                        ("the gate's running sum in bfloat16", bf16_gate_sum),
                        ("beta left out of the erase term", no_erase)):
        fn = with_chunk(wrong)
        report(seed, name, fn, params, params, tokens)
        delta_rule._chunk_xla = own_chunk
    if only_plain:
        continue
    gate = ling.decay_gate
    ling.decay_gate = lambda f, a_log, dt_bias, lower, width: jnp.repeat(
        gate(f, a_log, dt_bias, lower, width).reshape(
            *f.shape[:2], -1, width).mean(-1), width, axis=-1)
    report(seed, "one decay a head", kind.token_loss_fns(cfg, sizes)[0],
           params, params, tokens)
    ling.decay_gate = lambda f, a_log, dt_bias, lower, width: jnp.maximum(
        -jnp.repeat(jnp.exp(a_log.astype(jnp.float32)), width) * jax.nn.softplus(
            f.astype(jnp.float32) + dt_bias.astype(jnp.float32)), -10.0)
    report(seed, "the gate's softplus form without its bound",
           kind.token_loss_fns(cfg.replace(kda_lower_bound=-10.0), sizes)[0],
           params, params, tokens)
    ling.decay_gate = gate
