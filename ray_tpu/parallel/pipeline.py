"""Pipeline parallelism: GPipe-style microbatch pipeline over the 'pp' axis.

Reference has no native PP (SURVEY.md §2.4 — Alpa passthrough only). Here it
is a collective program: every stage runs the same SPMD code inside a
partial-manual shard_map over 'pp'; activations move stage-to-stage with
jax.lax.ppermute (point-to-point over ICI/DCN), and jax.grad differentiates
straight through the schedule (ppermute/scan have transpose rules), so the
backward pipeline comes for free.

Schedule: with M microbatches and P stages, T = M + P - 1 ticks; stage p
works on microbatch (t - p) at tick t (GPipe fill/drain bubble of (P-1)/M).

The model trunk must be expressible as stage_fn(stage_params, x) -> x, with
stage_params stacked on a leading 'stages' dim sharded P('pp'). Embedding /
head run outside the pipelined trunk under plain GSPMD.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_trunk(stage_fn: Callable, mesh, num_microbatches: int,
                   schedule: str = "gpipe"):
    """Returns trunk(stacked_params, x) -> y running the chosen schedule.

    stacked_params: pytree, each leaf [P_stages, ...] (sharded over 'pp').
    x: [B, ...] activations entering stage 0; y: same shape leaving the last
    stage (replicated over pp on exit).

    schedule:
      "gpipe" — forward scan differentiated by jax.grad; simple, but
        autodiff saves every tick's full carry (activation + the whole
        [M, ...] output bank), O(M^2) microbatch-activations per stage.
      "1f1b"  — explicit custom-vjp schedule (Megatron-LM PipeDream-flush
        style): the backward is a hand-written REVERSE pipeline over
        ppermute, each stage stashing exactly its M microbatch INPUTS and
        recomputing the stage forward inside vjp (remat). O(M)
        activations per stage and the same (P-1)/M fill/drain bubble.
        The trunk-level API means forward and backward remain separate
        phases (the loss head lives outside the trunk, so a trunk cannot
        start backward before the caller's loss runs) — the memory
        profile, not the phase interleaving, is what this trunk variant
        buys. For TRUE interleaved steady-state (per-microbatch head
        loss on the last stage, backward starting the next tick, O(pp)
        stash) use pipeline_train_1f1b below.
    """
    if schedule not in ("gpipe", "1f1b"):
        raise ValueError(f"schedule must be 'gpipe' or '1f1b', "
                         f"got {schedule!r}")
    if schedule == "1f1b":
        return _pipeline_trunk_1f1b(stage_fn, mesh, num_microbatches)
    pp = int(mesh.shape["pp"])
    M = num_microbatches

    def trunk_local(params_local, x):
        # params_local leaves: [1, ...] (this stage's slice); x: full [B,...]
        params_me = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index("pp")
        B = x.shape[0]
        mb = B // M
        xs = x.reshape((M, mb) + x.shape[1:])

        ticks = M + pp - 1
        fwd_perm = [(i, i + 1) for i in range(pp - 1)]

        def tick(carry, t):
            act, outs = carry
            # stage 0 ingests microbatch t (clamped); others take the permuted
            # activation from the previous stage.
            mb_idx = jnp.clip(t, 0, M - 1)
            inp0 = jax.lax.dynamic_index_in_dim(xs, mb_idx, keepdims=False)
            inp = jnp.where(stage == 0, inp0, act)
            out = stage_fn(params_me, inp)
            # last stage banks its result at slot t - (pp - 1)
            slot = jnp.clip(t - (pp - 1), 0, M - 1)
            valid = jnp.logical_and(stage == pp - 1, t >= pp - 1)
            cur = jax.lax.dynamic_index_in_dim(outs, slot, keepdims=False)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(valid, out, cur), slot, axis=0)
            # ship activation to the next stage (no wraparound)
            act_next = jax.lax.ppermute(out, "pp", fwd_perm)
            return (act_next, outs), None

        act0 = jnp.zeros((mb,) + x.shape[1:], x.dtype)
        outs0 = jnp.zeros_like(xs)
        (_, outs), _ = jax.lax.scan(tick, (act0, outs0),
                                    jnp.arange(ticks))
        # results live on the last stage only; zero elsewhere then psum to
        # replicate across pp.
        outs = jnp.where(stage == pp - 1, outs, jnp.zeros_like(outs))
        outs = jax.lax.psum(outs, "pp")
        return outs.reshape(x.shape)

    return jax.shard_map(
        trunk_local, mesh=mesh,
        in_specs=(P("pp"), P()),
        out_specs=P(),
        axis_names={"pp"}, check_vma=False)


def _pipeline_trunk_1f1b(stage_fn: Callable, mesh, num_microbatches: int):
    """Explicitly-scheduled pipeline: hand-written backward (reverse
    pipeline, reverse ppermute), per-stage input stash of exactly M
    microbatches, stage forward recomputed inside vjp (remat)."""
    pp = int(mesh.shape["pp"])
    M = num_microbatches
    fwd_perm = [(i, i + 1) for i in range(pp - 1)]
    rev_perm = [(i + 1, i) for i in range(pp - 1)]

    def _run_forward(params_me, stage, x):
        """GPipe fill/drain forward that ALSO returns each stage's input
        stash [M, mb, ...] (the residual the scheduled backward needs)."""
        B = x.shape[0]
        mb = B // M
        xs = x.reshape((M, mb) + x.shape[1:])
        ticks = M + pp - 1

        def tick(carry, t):
            act, outs, stash = carry
            mb_idx = jnp.clip(t, 0, M - 1)
            inp0 = jax.lax.dynamic_index_in_dim(xs, mb_idx, keepdims=False)
            inp = jnp.where(stage == 0, inp0, act)
            # this stage works on microbatch (t - stage)
            slot_in = jnp.clip(t - stage, 0, M - 1)
            valid_in = jnp.logical_and(t >= stage, t - stage < M)
            cur_in = jax.lax.dynamic_index_in_dim(stash, slot_in,
                                                  keepdims=False)
            stash = jax.lax.dynamic_update_index_in_dim(
                stash, jnp.where(valid_in, inp, cur_in), slot_in, axis=0)
            out = stage_fn(params_me, inp)
            slot = jnp.clip(t - (pp - 1), 0, M - 1)
            valid = jnp.logical_and(stage == pp - 1, t >= pp - 1)
            cur = jax.lax.dynamic_index_in_dim(outs, slot, keepdims=False)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(valid, out, cur), slot, axis=0)
            act_next = jax.lax.ppermute(out, "pp", fwd_perm)
            return (act_next, outs, stash), None

        act0 = jnp.zeros((mb,) + x.shape[1:], x.dtype)
        outs0 = jnp.zeros_like(xs)
        stash0 = jnp.zeros_like(xs)
        (_, outs, stash), _ = jax.lax.scan(tick, (act0, outs0, stash0),
                                           jnp.arange(ticks))
        outs = jnp.where(stage == pp - 1, outs, jnp.zeros_like(outs))
        outs = jax.lax.psum(outs, "pp")
        return outs.reshape(x.shape), stash

    @jax.custom_vjp
    def trunk_local(params_local, x):
        params_me = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index("pp")
        y, _ = _run_forward(params_me, stage, x)
        return y

    def trunk_fwd(params_local, x):
        params_me = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index("pp")
        y, stash = _run_forward(params_me, stage, x)
        return y, (params_me, stash)

    def trunk_bwd(res, g):
        params_me, stash = res
        # stash is [M, mb, ...]: recover the trunk input shape/dtype
        mb = stash.shape[1]
        x_shape = (M * mb,) + stash.shape[2:]
        x_dtype = stash.dtype
        stage = jax.lax.axis_index("pp")
        # the forward ends in psum(outs): under shard_map's transpose the
        # replicated output's cotangent arrives as per-device 1/pp shares
        # — psum reconstructs the true cotangent (without it every grad
        # lands exactly 1/pp of the autodiff-GPipe value)
        g = jax.lax.psum(g, "pp")
        gs = g.reshape((M, mb) + x_shape[1:]).astype(x_dtype)
        ticks = M + pp - 1

        def btick(carry, t):
            ct_in, dxs, dparams = carry
            # stage p back-props microbatch (t - (pp-1-p)): the cotangent
            # for mb m leaves the LAST stage at tick m and reaches stage
            # p (pp-1-p) ticks later via the reverse ring
            lag = (pp - 1) - stage
            m = jnp.clip(t - lag, 0, M - 1)
            valid = jnp.logical_and(t >= lag, t - lag < M)
            g_idx = jnp.clip(t, 0, M - 1)
            ct = jnp.where(stage == pp - 1,
                           jax.lax.dynamic_index_in_dim(gs, g_idx,
                                                        keepdims=False),
                           ct_in)
            inp = jax.lax.dynamic_index_in_dim(stash, m, keepdims=False)
            # stage forward recomputed here (remat); vjp w.r.t. params+input
            _, vjp_fn = jax.vjp(stage_fn, params_me, inp)
            dp, dx = vjp_fn(ct.astype(x_dtype))
            dparams = jax.tree.map(
                lambda acc, d: acc + jnp.where(valid, d, 0.0).astype(acc.dtype),
                dparams, dp)
            cur = jax.lax.dynamic_index_in_dim(dxs, m, keepdims=False)
            bank = jnp.logical_and(valid, stage == 0)
            dxs = jax.lax.dynamic_update_index_in_dim(
                dxs, jnp.where(bank, dx, cur), m, axis=0)
            ct_next = jax.lax.ppermute(jnp.where(valid, dx, 0.0),
                                       "pp", rev_perm)
            return (ct_next, dxs, dparams), None

        ct0 = jnp.zeros((mb,) + x_shape[1:], x_dtype)
        dxs0 = jnp.zeros((M, mb) + x_shape[1:], x_dtype)
        dparams0 = jax.tree.map(lambda a: jnp.zeros_like(a, jnp.float32),
                                params_me)
        (_, dxs, dparams), _ = jax.lax.scan(
            btick, (ct0, dxs0, dparams0), jnp.arange(ticks))
        # x entered replicated (in_specs P()): shard_map's transpose sums
        # the per-device cotangents itself, so return the LOCAL
        # contribution (real values only on stage 0, zeros elsewhere) —
        # an explicit psum here would double-count by pp
        dx_full = dxs.reshape(x_shape)
        # params_local leaves are [1, ...] slices: cotangent matches
        dparams_local = jax.tree.map(lambda d, p: d[None].astype(p.dtype),
                                     dparams, params_me)
        return dparams_local, dx_full

    trunk_local.defvjp(trunk_fwd, trunk_bwd)

    return jax.shard_map(
        trunk_local, mesh=mesh,
        in_specs=(P("pp"), P()),
        out_specs=P(),
        axis_names={"pp"}, check_vma=False)


def pipeline_train_1f1b(stage_fn: Callable, head_loss_fn: Callable, mesh,
                        num_microbatches: int):
    """TRUE interleaved 1F1B (Megatron-LM PipeDream-flush): one scheduled
    program computes loss AND grads, with the backward of microbatch f
    starting the tick after its forward leaves the last stage — steady
    state alternates one forward and one backward per stage.

    This is what the trunk-level API (schedule="1f1b" above) cannot
    express: there the loss head runs outside the trunk, so forward and
    backward remain separate phases. Here head_loss_fn runs ON the last
    stage at each forward tick and its cotangent enters the reverse ring
    immediately. Peak stash is a min(pp, M)-deep ring of microbatch
    inputs (vs M for the phase-split schedule).

    Schedule (0-indexed): stage p runs fwd of microbatch f at tick
    p + 2f and bwd of f at tick (2*pp - 1 - p) + 2f; fwd/bwd ticks have
    opposite parity per stage, so each tick is exactly one unit of work,
    selected with lax.cond (the unused branch is not computed).
    Total ticks 2M + 2pp - 2; bubble (pp-1)/M, same as GPipe.

    Args:
      stage_fn(stage_params, x) -> y               (trunk slice)
      head_loss_fn(head_params, y_mb, target_mb) -> scalar (per-mb loss)
    Returns:
      step(stacked_params, head_params, x, targets)
        -> (loss, d_stacked, d_head, dx)
      loss = mean over microbatches; d_stacked matches stacked_params
      ([pp, ...] sharded over 'pp'); dx is the cotangent w.r.t. x (for
      an embedding outside the pipeline).
    """
    pp = int(mesh.shape["pp"])
    M = num_microbatches
    W = min(pp, M)                       # stash ring depth
    fwd_perm = [(i, i + 1) for i in range(pp - 1)]
    rev_perm = [(i + 1, i) for i in range(pp - 1)]
    ticks = 2 * M + 2 * pp - 2

    def step_local(params_local, head_params, x, targets):
        params_me = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index("pp")
        last = pp - 1
        B = x.shape[0]
        mb = B // M
        xs = x.reshape((M, mb) + x.shape[1:])
        ts = targets.reshape((M, mb) + targets.shape[1:])

        def fwd_unit(operand):
            params_me, inp, head_params, tgt, is_last = operand
            y = stage_fn(params_me, inp)

            def with_head(_):
                (loss_mb, (dh, dy)) = jax.value_and_grad(
                    head_loss_fn, argnums=(0, 1))(head_params, y, tgt)
                return loss_mb, dh, dy

            def no_head(_):
                zh = jax.tree.map(jnp.zeros_like, head_params)
                return jnp.zeros((), jnp.float32), zh, jnp.zeros_like(y)

            loss_mb, dh, dy = jax.lax.cond(is_last, with_head, no_head,
                                           None)
            return y, loss_mb, dh, dy

        def bwd_unit(operand):
            params_me, inp, ct = operand
            _, vjp_fn = jax.vjp(stage_fn, params_me, inp)
            dp, dx = vjp_fn(ct.astype(inp.dtype))
            return dp, dx

        def tick(carry, t):
            (act_in, ct_in, stash, dy_buf, dxs, dparams, dhead,
             loss) = carry
            # schedule decode for this (stage, tick)
            tf = t - stage
            do_fwd = jnp.logical_and(
                jnp.logical_and(tf >= 0, tf % 2 == 0), tf // 2 < M)
            f_fwd = jnp.clip(tf // 2, 0, M - 1)
            tb = t - (2 * pp - 1 - stage)
            do_bwd = jnp.logical_and(
                jnp.logical_and(tb >= 0, tb % 2 == 0), tb // 2 < M)
            f_bwd = jnp.clip(tb // 2, 0, M - 1)

            # ---- forward unit -------------------------------------------
            inp0 = jax.lax.dynamic_index_in_dim(xs, f_fwd, keepdims=False)
            inp = jnp.where(stage == 0, inp0, act_in)
            tgt = jax.lax.dynamic_index_in_dim(ts, f_fwd, keepdims=False)

            def run_fwd(_):
                return fwd_unit((params_me, inp, head_params, tgt,
                                 stage == last))

            def skip_fwd(_):
                zh = jax.tree.map(jnp.zeros_like, head_params)
                return (jnp.zeros_like(inp), jnp.zeros((), jnp.float32),
                        zh, jnp.zeros_like(inp))

            y, loss_mb, dh, dy = jax.lax.cond(do_fwd, run_fwd, skip_fwd,
                                              None)
            # stash this fwd's input for its backward (ring slot f mod W)
            slot = f_fwd % W
            cur = jax.lax.dynamic_index_in_dim(stash, slot, keepdims=False)
            stash = jax.lax.dynamic_update_index_in_dim(
                stash, jnp.where(do_fwd, inp, cur), slot, axis=0)
            dy_buf = jnp.where(jnp.logical_and(do_fwd, stage == last),
                               dy, dy_buf)
            loss = loss + jnp.where(do_fwd, loss_mb, 0.0)
            dhead = jax.tree.map(
                lambda acc, d: acc + jnp.where(do_fwd, d, 0.0
                                               ).astype(acc.dtype),
                dhead, dh)

            # ---- backward unit ------------------------------------------
            ct = jnp.where(stage == last, dy_buf, ct_in)
            slot_b = f_bwd % W
            inp_b = jax.lax.dynamic_index_in_dim(stash, slot_b,
                                                 keepdims=False)

            def run_bwd(_):
                return bwd_unit((params_me, inp_b, ct))

            def skip_bwd(_):
                return (jax.tree.map(jnp.zeros_like, params_me),
                        jnp.zeros_like(inp_b))

            dp, dx = jax.lax.cond(do_bwd, run_bwd, skip_bwd, None)
            dparams = jax.tree.map(
                lambda acc, d: acc + jnp.where(do_bwd, d, 0.0
                                               ).astype(acc.dtype),
                dparams, dp)
            curx = jax.lax.dynamic_index_in_dim(dxs, f_bwd, keepdims=False)
            bank = jnp.logical_and(do_bwd, stage == 0)
            dxs = jax.lax.dynamic_update_index_in_dim(
                dxs, jnp.where(bank, dx, curx), f_bwd, axis=0)

            # ---- ring exchange (all stages participate every tick) ------
            act_next = jax.lax.ppermute(jnp.where(do_fwd, y, 0.0),
                                        "pp", fwd_perm)
            ct_next = jax.lax.ppermute(jnp.where(do_bwd, dx, 0.0),
                                       "pp", rev_perm)
            return (act_next, ct_next, stash, dy_buf, dxs, dparams,
                    dhead, loss), None

        shp = (mb,) + x.shape[1:]
        carry0 = (
            jnp.zeros(shp, x.dtype),                        # act_in
            jnp.zeros(shp, x.dtype),                        # ct_in
            jnp.zeros((W,) + shp, x.dtype),                 # stash ring
            jnp.zeros(shp, x.dtype),                        # dy_buf
            jnp.zeros((M,) + shp, x.dtype),                 # dxs bank
            jax.tree.map(lambda a: jnp.zeros_like(a, jnp.float32),
                         params_me),                        # dparams
            jax.tree.map(lambda a: jnp.zeros_like(a, jnp.float32),
                         head_params),                      # dhead
            jnp.zeros((), jnp.float32),                     # loss
        )
        (_, _, _, _, dxs, dparams, dhead, loss), _ = jax.lax.scan(
            tick, carry0, jnp.arange(ticks))

        # owners: loss/dhead live on the last stage, dxs on stage 0 —
        # zero the others and psum to replicate
        loss = jax.lax.psum(jnp.where(stage == last, loss, 0.0), "pp") / M
        dhead = jax.tree.map(
            lambda d: jax.lax.psum(
                jnp.where(stage == last, d, 0.0), "pp") / M, dhead)
        dxs = jax.lax.psum(jnp.where(stage == 0, dxs,
                                     jnp.zeros_like(dxs)), "pp")
        dx = dxs.reshape(x.shape) / M
        dparams_local = jax.tree.map(
            lambda d, p: (d / M)[None].astype(jnp.float32),
            dparams, params_me)
        return loss, dparams_local, dhead, dx

    return jax.shard_map(
        step_local, mesh=mesh,
        in_specs=(P("pp"), P(), P(), P()),
        out_specs=(P(), P("pp"), P(), P()),
        axis_names={"pp"}, check_vma=False)


def stack_stages(layers_params, pp: int):
    """Reshape stacked per-layer params [L, ...] -> [pp, L//pp, ...]."""
    def r(a):
        L = a.shape[0]
        assert L % pp == 0, f"n_layers {L} not divisible by pp={pp}"
        return a.reshape((pp, L // pp) + a.shape[1:])

    return jax.tree.map(r, layers_params)


def unstack_stages(stacked):
    def r(a):
        return a.reshape((-1,) + a.shape[2:])

    return jax.tree.map(r, stacked)
