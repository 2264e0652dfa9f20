"""Which part of the model issued each device op, from the trace itself.

The step program opens ``jax.named_scope``s (PERF.md 3 has the list), and
XLA carries an instruction's scope path as its ``op_name``. In a chip
trace that path is the stat ``tf_op`` of the op's ``XEventMetadata`` on
the device plane, beside ``hlo_category``; the metadata's ``name`` is the
whole HLO line, the key of ``trace_reduce``'s ``device_ops``.
``jax.profiler.ProfileData`` returns an event's own stats only, so the
file is read here from the wire format, the five messages that hold the
label and nothing else (no protobuf module is installed):

  XSpace          1 planes
  XPlane          2 name, 3 lines (skipped by their length), 4
                  event_metadata and 5 stat_metadata (maps: 1 key, 2 value)
  XEventMetadata  2 name, 5 stats
  XStat           1 metadata_id, 5 str_value, 7 ref_value (the id of a
                  stat metadata whose name is the string)
  XStatMetadata   1 id, 2 name

Below ``read_file`` pure functions: ``elements`` (a path's parts),
``bucket`` (the one part of the model an op belongs to), ``which_pass``
(forward, the checkpoint's replay, backward), ``table`` (seconds by bucket
and pass). By hand, after a ``--trace 1`` run (or on one ``.xplane.pb``):

    python3 -m benchmark.op_scopes benchmark/out/trace

prints milliseconds a step by bucket and pass, then what the scopes inside
a bucket say: the feed-forward's sub-scopes, the optimizer as update /
``rule`` / ``grad_norm``, the parts of a prediction module (``mtp``), the
kernel-call scopes with their calls a step, the largest unscoped ops.
"""

from __future__ import annotations

import functools
import os
import re
import sys

from benchmark.trace_reduce import DEVICE_PLANE, find_xplane

# where both kinds trace to, and what ``run.py`` clears before each run
TRACE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "out", "trace")
# the program's vocabulary: an op is in the first of these on its path
PARTS = ("embed", "attention", "mixer", "feed_forward", "head_loss",
         "optimizer")
LOOP = "layers"           # under it and in no part: the layer loop's own
BUCKETS = PARTS + ("layer_loop", "unscoped")
PASSES = ("forward", "replay", "backward", "none")
# inside ``feed_forward`` (the innermost on the path counts)
SUB_SCOPES = ("router", "dispatch", "experts", "combine", "shared")
# inside ``optimizer``: a family's ``post_update`` and the gradient's norm;
# what is in neither is the optimizer's own update and the parameter add
OPTIMIZER_SCOPES = ("rule", "grad_norm")
MODULE = "mtp"            # round a prediction module's parts, in any bucket
# a kernel call's scope says which path its plan chose
KERNEL = re.compile(r"^(flash\.(fwd|dq|dkdv)|ssd\.(fwd|bwd)|gmm|tgmm|tp)"
                    r"\.([a-z]+)$")
_WRAPPED = re.compile(r"^(jvp|transpose|vmap)\((.*)\)$")
WANTED = ("tf_op", "hlo_category")


# --- the wire format ----------------------------------------------------


def _varint(buf, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, start: int, end: int):
    """``(field number, value)`` of one message: an int for a varint, the
    ``(start, end)`` of a length-delimited field (nothing of it is
    touched), None for a fixed one."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"wire type {wire} at byte {i}: no XSpace")
        yield key >> 3, value


def _text(buf, span: tuple) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_value(buf, span: tuple):
    for no, value in _fields(buf, *span):
        if no == 2:
            return value
    return None


def _plane(buf, span: tuple):
    """``{HLO line: {"tf_op": ..., "hlo_category": ...}}`` of one plane,
    None for a plane that is no device's."""
    name, events, stats = "", [], []
    for no, value in _fields(buf, *span):
        if no == 2:
            name = _text(buf, value)
        elif no == 4:
            events.append(value)
        elif no == 5:
            stats.append(value)
    if not DEVICE_PLANE.match(name):
        return None
    stat_names = {}
    for entry in stats:
        meta = _map_value(buf, entry)
        if meta is None:
            continue
        got = dict(_fields(buf, *meta))
        if 1 in got and 2 in got:
            stat_names[got[1]] = _text(buf, got[2])
    out = {}
    for entry in events:
        meta = _map_value(buf, entry)
        if meta is None:
            continue
        line, found = None, {}
        for no, value in _fields(buf, *meta):
            if no == 2:
                line = _text(buf, value)
            elif no == 5:
                stat = dict(_fields(buf, *value))
                what = stat_names.get(stat.get(1))
                if what in WANTED:
                    found[what] = (_text(buf, stat[5]) if 5 in stat
                                   else stat_names.get(stat.get(7), ""))
        if line is not None:
            out[line] = found
    return out


def read_file(path: str) -> dict:
    """Every device plane's event metadata of one ``.xplane.pb``, joined
    (the chips of one program hold the same lines)."""
    return _read(path, os.path.getmtime(path))


@functools.lru_cache(maxsize=1)           # every metric of a run asks
def _read(path: str, mtime: float) -> dict:
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for no, span in _fields(buf, 0, len(buf)):
        if no == 1:
            out.update(_plane(buf, span) or {})
    return out


def of_run(trace_dir: str = None):
    """The labels of the traced run that wrote into ``trace_dir``
    (``TRACE_DIR`` unless given), or None: no trace there."""
    try:
        return read_file(find_xplane(trace_dir or TRACE_DIR))
    except FileNotFoundError:
        return None


# --- from a path to a part of the model ---------------------------------


def elements(tf_op: str) -> list:
    """``jit(step)/transpose(jvp(layers))/while/body/attention/mul:`` ->
    ``[jit(step), transpose(, jvp(, layers, while, body, attention, mul]``:
    the path's parts, each ``jvp(...)``, ``transpose(...)`` and
    ``vmap(...)`` taken off what it wraps and kept before it with its
    bracket, so that the wrapper ``transpose(`` (the backward pass) is not
    the primitive ``transpose`` a path may end in. Of a fusion that names
    several instructions (``a;b``) the first."""
    out = []
    for part in (tf_op or "").split(";")[0].rstrip(":").split("/"):
        m = _WRAPPED.match(part)
        while m:
            out.append(m.group(1) + "(")
            part = m.group(2)
            m = _WRAPPED.match(part)
        if part:
            out.append(part)
    return out


def bucket(parts: list) -> str:
    """Exactly one of ``BUCKETS``. An op under ``layers`` and in no part
    is the loop's own when the loop's body or condition issued it
    (``.../while/body/dynamic_update_slice``) or it stands round the loop
    (the stacks' zeros). One whose path ENDS in ``while`` was made by the
    compiler inside the body (a fusion merged from two parts' ops, a
    relayout copy) and given the loop instruction's own name: the work of
    some part, nobody can say which, so ``unscoped``."""
    for p in parts:
        if p in PARTS:
            return p
    return "layer_loop" if LOOP in parts and parts[-1] != "while" \
        else "unscoped"


def which_pass(parts: list) -> str:
    """By jax's wrappers alone: ``.../attention/transpose`` is the forward's
    ``q.transpose(0, 2, 1, 3)``."""
    if "rematted_computation" in parts:
        return "replay"
    if "transpose(" in parts:
        return "backward"
    return "forward" if "jvp(" in parts else "none"


def sub_scope(parts: list, among: tuple = SUB_SCOPES):
    """The innermost of ``among`` on the path, or None."""
    return next((p for p in reversed(parts) if p in among), None)


def kernel_scope(parts: list):
    """The kernel-call scope on the path (``flash.dkdv.resident``), or
    None."""
    return next((p for p in reversed(parts) if KERNEL.match(p)), None)


def labelled(device_ops: list, labels: dict) -> list:
    """``[(HLO line, seconds, the parts of its path), ...]``."""
    return [(name, s, elements((labels.get(name) or {}).get("tf_op")))
            for name, s in device_ops]


def table(device_ops: list, labels: dict):
    """``{(bucket, pass): seconds}`` over ``device_ops`` (``trace_reduce``:
    self times, so the sum is the busy time), or None where no op carries
    a scope of the vocabulary: a program without scopes reads nothing."""
    out, scoped = {}, False
    for _, s, parts in labelled(device_ops, labels):
        key = (bucket(parts), which_pass(parts))
        scoped = scoped or key[0] != "unscoped"
        out[key] = out.get(key, 0.0) + s
    return out if scoped else None


# --- by hand ------------------------------------------------------------


def _steps(modules: list) -> tuple:
    """``(name, executions)`` of the program that holds most of the time."""
    total, count = {}, {}
    for s, e, name in modules:
        total[name] = total.get(name, 0) + e - s
        count[name] = count.get(name, 0) + 1
    name = max(total, key=total.get)
    return name, count[name]


def report(path: str, out=None) -> None:
    from benchmark import trace_reduce as tr

    devices, _ = tr.read_planes(path)
    red = tr.reduce_planes([tr.reduce_plane(o, m) for o, m in devices])
    labels = read_file(path)
    program, steps = _steps(devices[0][1])
    ms = lambda s: 1e3 * s / steps                              # noqa: E731
    ops = labelled(red["device_ops"], labels)
    say = lambda line="": print(line, file=out)                 # noqa: E731
    say(f"{path}: {red['devices']} device(s), {steps} executions of "
        f"{program}, busy {ms(red['busy_s']):.1f} ms a step; "
        f"{len(labels)} labelled instructions")

    def rows(by: dict, names: tuple, indent: str = "") -> None:
        """``by`` is ``{(name, pass): seconds}``; rows of all zeros are
        left out, the last row sums what was printed."""
        width = 14 - len(indent)
        total = [0.0] * len(PASSES)
        for n in names:
            row = [by.get((n, p), 0.0) for p in PASSES]
            if any(row) or not indent:
                total = [t + v for t, v in zip(total, row)]
                say(f"{indent}{n:<{width}}" + "".join(
                    f"{ms(v):10.2f}" for v in row) + f"{ms(sum(row)):10.2f}")
        say(f"{indent}{'all':<{width}}" + "".join(
            f"{ms(v):10.2f}" for v in total) + f"{ms(sum(total)):10.2f}")

    say("ms a step     " + "".join(f"{p:>10}" for p in PASSES) + "       all")
    rows(table(red["device_ops"], labels) or {}, BUCKETS)
    subs, steps_of, module, kernels, unscoped = {}, {}, {}, {}, []
    for name, s, parts in ops:
        b, p = bucket(parts), which_pass(parts)
        if b == "feed_forward":
            key = (sub_scope(parts) or "(none)", p)
            subs[key] = subs.get(key, 0.0) + s
        if b == "optimizer":
            key = sub_scope(parts, OPTIMIZER_SCOPES) or "update"
            steps_of[key] = steps_of.get(key, 0.0) + s
        if MODULE in parts:
            module[(b, p)] = module.get((b, p), 0.0) + s
        k = kernel_scope(parts)
        if k is not None:
            calls = red["op_calls"][name] if tr.opcode(name) in (
                "custom-call", "collective-permute-start") else 0
            at = kernels.setdefault((k, b, p), [0.0, 0.0])
            at[0] += s
            at[1] += calls
        if b == "unscoped":
            unscoped.append((s, name, parts))
    if subs:
        say("feed_forward, ms a step")
        rows(subs, SUB_SCOPES + ("(none)",), "  ")
    if steps_of:
        say("optimizer, ms a step: " + ", ".join(
            f"{k} {ms(steps_of[k]):.3f}"
            for k in ("update",) + OPTIMIZER_SCOPES if k in steps_of))
    if module:
        say(f"of each part under {MODULE} (the prediction module), ms a step")
        rows(module, BUCKETS, "  ")
    if kernels:
        say("kernel-call scopes: ms a step, Mosaic calls or permutes a step")
        for (k, b, p), (s, calls) in sorted(kernels.items()):
            say(f"  {k:<22}{b:<14}{p:<10}{ms(s):10.2f}{calls / steps:8.1f}")
    why = {}
    for s, name, parts in unscoped:
        key = ("named after a loop" if LOOP in parts else "another name"
               if parts else "no op_name")
        why[key] = why.get(key, 0.0) + s
    say("unscoped, ms a step: " + ", ".join(
        f"{k} {ms(v):.2f}" for k, v in sorted(why.items())))
    say("largest unscoped ops, ms a step")
    for s, name, parts in sorted(unscoped, reverse=True)[:20]:
        label = labels.get(name) or {}
        say(f"  {ms(s):9.3f}  {tr.short_name(name)}  "
            f"[{label.get('hlo_category', '')}] "
            f"{label.get('tf_op') or 'no op_name'}")


if __name__ == "__main__":
    where = sys.argv[1] if len(sys.argv) > 1 else TRACE_DIR
    report(where if os.path.isfile(where) else find_xplane(where))
