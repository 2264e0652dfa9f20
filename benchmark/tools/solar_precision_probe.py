"""What decides ``correct`` in a train_solar cell, read at the cell's real size on the
chip for the program as it is (every seed given) and, on the first seed, for wrong
programs: the KDA projections rounded to 8 bits (the nearest precision below the
configuration's bf16), beta without its factor 2, Ling's safe gate in place of the
softplus form, the gate clamped at the old kernel's bound (-5 a step), ONE output
gate a head in place of a gate a channel, the grouped-query gate left out, the bias
left out of the choice, and (on the op's plain path, ``kda_impl`` "xla", forward
only: no kernel takes a wrong recurrence) the state carried in bfloat16 from chunk
to chunk, beside that path as it is. One process, no cluster; prints one JSON line a
case.

    chiprun --chips 1 -- python3 benchmark/tools/solar_precision_probe.py <cell> [--op] [seed ...]

``--op``: part (e) alone, the delta rule's calls on the first KDA layer's scan inputs
at the cell's shape through kind ``train_kda``'s ``op_agreement``, every seed given:
the kernel pair (told no bound) in the timed type and on the same values in float32,
and on the float32 values the plain path with its state rounded to bfloat16, with its
gate clamped at -5 and with its gate's running sum rounded to bfloat16; each line says the largest of the six parts beside the limit
of the cell's ``check`` it is held to, and the share of the gate under -5 and -11.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import model_solar, resolve  # noqa: E402
from benchmark.kinds import train_solar as kind  # noqa: E402
from benchmark.kinds.train_kda import op_agreement  # noqa: E402
from ray_tpu.models import solar  # noqa: E402
from ray_tpu.ops import delta_rule  # noqa: E402

cell = resolve.cell(sys.argv[1])
only_op = "--op" in sys.argv
seeds = [int(s) for s in sys.argv[2:] if not s.startswith("--")] or [2147483659]
recipe, mix = cell["train"], cell["mix"]
sizes = model_solar.sizes(cell["config"])
cfg = model_solar.solar_config(cell["config"], **{k: recipe[k] for k in (
    "attn_impl", "gmm_impl", "kda_impl", "remat", "f32_logits") if k in recipe})
print("device", jax.devices()[0].device_kind, flush=True)
program, reference = kind.token_loss_fns(cfg, sizes)
plain = cfg.replace(kda_impl="xla")


def each(params, fn):
    return dict(params, layers=[fn(run) for run in params["layers"]])


# reduce_precision and not a cast there and back: on the TPU the compiler may drop
# such a pair of casts (PERF.md 6, PR 26)
_rounded = jax.jit(lambda w: jax.lax.reduce_precision(w, exponent_bits=4,
                                                      mantissa_bits=3))
_bf16 = lambda x: jax.lax.reduce_precision(x, exponent_bits=8,    # noqa: E731
                                           mantissa_bits=7)


def report(seed, name, fn, p, params, tokens):
    got, routes, _ = fn(p, tokens)
    ref, _, rec = reference(params, tokens, routes)
    print(json.dumps({"seed": seed, "case": name,
                      **kind.loss_agreement(got, ref),
                      **kind.route_agreement(routes, rec, cfg.top_k)}),
          flush=True)


def bf16_state(own):
    def chunk(state, *a):
        state, o = own(state, *a)
        return _bf16(state), o
    return chunk


def clamped_gate(own):
    def chunk(state, q, k, v, g, beta):
        return own(state, q, k, v, jnp.maximum(g, -5.0), beta)
    return chunk


def bf16_gate_sum(own):
    def chunk(state, q, k, v, g, beta):
        # a gate whose running sum is the bfloat16 of the true one
        cum = _bf16(jnp.cumsum(g, axis=2))
        g = jnp.diff(cum, axis=2, prepend=jnp.zeros_like(cum[:, :, :1]))
        return own(state, q, k, v, jnp.minimum(g, 0.0), beta)
    return chunk


def op_cases(seed, params, tokens):
    """Part (e): the kind's own reading, for the calls as they are and for
    wrong recurrences on the plain path."""
    tol = recipe["check"]
    inputs = jax.jit(lambda p, t: kind.scan_inputs(cfg, p, t))(params, tokens)
    under = [float(jnp.mean(inputs[3] < b)) for b in kind.OLD_BOUNDS]
    read = op_agreement(
        inputs, jax.random.normal(jax.random.PRNGKey(seed % (2 ** 31)),
                                  inputs[2].shape, jnp.float32), None)
    as_it_is = lambda *a: delta_rule.gated_delta_rule(         # noqa: E731
        *a, impl=cfg.kda_impl, lower_bound=None)

    def on_plain(*a):
        with jax.default_matmul_precision("highest"):
            return delta_rule.gated_delta_rule(*a, impl="xla",
                                               lower_bound=None)

    own_chunk = delta_rule._chunk_xla
    for name, scan, which, chunk in (
            ("the calls as they are", as_it_is, "timed", None),
            ("the calls as they are", as_it_is, "float32", None),
            ("the state in bfloat16", on_plain, "float32", bf16_state),
            ("the gate clamped at -5", on_plain, "float32", clamped_gate),
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
                          "gate_under": under,
                          "seconds": round(time.perf_counter() - t0, 1)}),
              flush=True)


def patched(name, fn):
    """The program's forward with ``solar.<name>`` replaced while it
    traces (at its first call, inside ``report``)."""
    own = getattr(solar, name)
    setattr(solar, name, fn(own))
    return kind.token_loss_fns(cfg, sizes)[0], lambda: setattr(solar, name, own)


for seed in seeds:
    params = jax.jit(lambda k: kind.seeded_weights(k, cfg))(
        jax.random.PRNGKey(seed % (2 ** 31)))
    tokens = jax.random.randint(jax.random.PRNGKey((seed + 1) % (2 ** 31)),
                                (mix["batch"], mix["seq"] + 1), 0,
                                cfg.vocab_size, "int32")
    if only_op:
        op_cases(seed, params, tokens)
        continue
    report(seed, "as it is", program, params, params, tokens)
    if seed != seeds[0]:
        continue
    zero = jax.jit(lambda w: w * 0)
    kda = ("wq", "wk", "wv", "w_decay_a", "w_decay_b", "w_gate_a", "w_gate_b",
           "wo")
    H, dk = cfg.kda_heads, cfg.kda_head_dim
    a_head = jax.jit(lambda w: jnp.broadcast_to(
        w.reshape(*w.shape[:2], H, dk).astype(jnp.float32).mean(
            axis=-1, keepdims=True), (*w.shape[:2], H, dk)).reshape(
                w.shape).astype(w.dtype))
    for name, p in {
            "8-bit KDA projections": each(params, lambda s: {
                k: (_rounded(w) if k in kda else w) for k, w in s.items()}
                if "a_log" in s else s),
            "8-bit GQA projections": each(params, lambda s: {
                k: (_rounded(w) if k in ("wq", "wk", "wv", "wo",
                                         "w_attn_gate") else w)
                for k, w in s.items()} if "w_attn_gate" in s else s),
            "one output gate a head in place of a channel": each(
                params, lambda s: {**s, "w_gate_b": a_head(s["w_gate_b"])}
                if "w_gate_b" in s else s),
            "the grouped-query gate left out": each(params, lambda s: {
                k: w for k, w in s.items() if k != "w_attn_gate"}),
            "the bias left out of the choice": each(params, lambda s: {
                **s, "router_bias": zero(s["router_bias"])})}.items():
        report(seed, name, program, p, params, tokens)
        del p
    for name, what, fn in (
            ("beta without its factor 2", "scan_inputs",
             lambda own: lambda h, lp, c: (lambda q, k, v, g, beta: (
                 q, k, v, g, 0.5 * beta))(*own(h, lp, c))),
            ("the safe gate in place of the softplus form", "decay_gate",
             lambda own: lambda f, a_log, dt_bias, width:
             -5.0 * jax.nn.sigmoid(jnp.repeat(jnp.exp(a_log.astype(
                 jnp.float32)), width) * (f.astype(jnp.float32)
                                          + dt_bias.astype(jnp.float32)))),
            ("the gate clamped at -5", "decay_gate",
             lambda own: lambda *a: jnp.maximum(own(*a), -5.0))):
        wrong, undo = patched(what, fn)
        report(seed, name, wrong, params, params, tokens)
        undo()
    report(seed, "the plain path as it is",
           kind.token_loss_fns(plain, sizes)[0], params, params, tokens)
    own_chunk = delta_rule._chunk_xla
    delta_rule._chunk_xla = bf16_state(own_chunk)
    report(seed, "the state in bfloat16", kind.token_loss_fns(plain, sizes)[0],
           params, params, tokens)
    delta_rule._chunk_xla = own_chunk
