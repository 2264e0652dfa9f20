"""How ``tiny_scoped.xplane.pb`` was recorded (on the chip, PR 36): three
steps of a small training program that opens the step program's named
scopes (PERF.md 3), python and host tracers off so that the file stays
small. Two scanned, checkpointed layers of two halves each (``attention``
with a ``custom_vjp`` whose backward rule opens a kernel-call scope, then
``feed_forward``; ``mixer``, then ``feed_forward``), an ``embed``, a
``head_loss``, a prediction module whose embedding and head open the same
scopes under ``mtp``, and an ``optimizer`` with a ``rule`` and a
``grad_norm`` under ``value_and_grad``, and beside the step a program with
no scope at all, as a cell's batch draw is.

    python3 benchmark/tests/record_scoped_trace.py <out_dir>
"""
import glob
import shutil
import sys
import time

import jax
import jax.numpy as jnp

out = sys.argv[1]
V, D, B, S, L = 512, 256, 4, 128, 3


@jax.custom_vjp
def mix(x, w):
    with jax.named_scope("flash.fwd.loop"):
        return jnp.tanh(x @ w)


def mix_fwd(x, w):
    return mix(x, w), (x, w)


def mix_bwd(res, g):
    x, w = res
    with jax.named_scope("flash.dq.loop"):
        y = jnp.tanh(x @ w)
        g = g * (1 - y * y)
        return g @ w.T, jnp.einsum("bsd,bse->de", x, g)


mix.defvjp(mix_fwd, mix_bwd)


def half(name):
    def layer(x, lp):
        with jax.named_scope(name):
            x = x + mix(x, lp["w"])
        with jax.named_scope("feed_forward"):
            x = x + jax.nn.silu(x @ lp["up"]) @ lp["down"]
        return x, None
    return jax.checkpoint(layer)


def cross_entropy(logits, ids):
    picked = jnp.take_along_axis(logits, ids[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)


def loss_fn(params, tokens):
    with jax.named_scope("embed"):
        x = params["embed"][tokens[:, :-1]]
    for name in ("attention", "mixer"):
        with jax.named_scope("layers"):
            x, _ = jax.lax.scan(half(name), x, params[name])
    with jax.named_scope("head_loss"):
        loss = cross_entropy(x @ params["head"], tokens[:, 1:])
    with jax.named_scope("mtp"):
        with jax.named_scope("embed"):
            ahead = params["embed"][tokens[:, 1:]]
        with jax.named_scope("head_loss"):
            more = cross_entropy(jnp.tanh(x + ahead) @ params["head"],
                                 tokens[:, 1:])
    return loss + 0.3 * more


@jax.jit
def step(params, tokens):
    loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
    with jax.named_scope("optimizer"):
        # clipped by the leaf's largest entry: a pass of its own, which XLA
        # cannot merge into the norm's reduction over the same gradient
        params = jax.tree.map(
            lambda p, g: p - 0.01 * g / jnp.maximum(1.0, jnp.max(jnp.abs(g))),
            params, grads)
        with jax.named_scope("rule"):       # as a family's ``post_update``
            head = params["head"]
            params = {**params, "head": head / jnp.maximum(
                1.0, jnp.max(jnp.abs(head)))}
        with jax.named_scope("grad_norm"):
            norm = jnp.sqrt(sum(jnp.sum(g * g)
                                for g in jax.tree.leaves(grads)))
    return params, loss, norm


@jax.jit
def draw(key, i):           # no scope: a cell's batch program
    return jax.random.randint(jax.random.fold_in(key, i), (B, S + 1), 0, V)


def init(key):
    ks = iter(jax.random.split(key, 12))
    mat = lambda *shape: jax.random.normal(                     # noqa: E731
        next(ks), shape, jnp.float32) * shape[-2] ** -0.5
    stack = lambda: {"w": mat(L, D, D), "up": mat(L, D, 2 * D),  # noqa: E731
                     "down": mat(L, 2 * D, D)}
    return {"embed": mat(V, D), "attention": stack(), "mixer": stack(),
            "head": mat(D, V)}


key = jax.random.PRNGKey(0)
params = jax.jit(init)(key)
params, loss, _ = step(params, draw(key, 0))
jax.block_until_ready(params)
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
opts.host_tracer_level = 0
jax.profiler.start_trace(out + "/scoped_trace", profiler_options=opts)
t0 = time.perf_counter()
for i in range(3):
    params, loss, _ = step(params, draw(key, i + 1))
    float(loss)
    time.sleep(0.002)
span = time.perf_counter() - t0
jax.profiler.stop_trace()
path = glob.glob(out + "/scoped_trace/plugins/profile/*/*.xplane.pb")[0]
shutil.copy(path, out + "/tiny_scoped.xplane.pb")
print("recorded", path, "span_s", span, "loss", float(loss),
      jax.devices()[0].device_kind)
