"""The scope reader (``op_scopes.py``, ``readers/scope_share.py``) on two
small traces recorded on the chip: ``tiny_tpu.xplane.pb`` (PR 23, a
program without scopes) and ``tiny_scoped.xplane.pb``
(``record_scoped_trace.py``: three steps of a small training program that
opens the step program's scopes), and on the path forms jax 0.9.0 gives."""
import os
import re

import pytest

from benchmark import op_scopes as sc
from benchmark import trace_reduce as tr
from benchmark.readers import scope_share

HERE = os.path.dirname(__file__)
TINY = os.path.join(HERE, "tiny_tpu.xplane.pb")
SCOPED = os.path.join(HERE, "tiny_scoped.xplane.pb")

# PERF.md 3: what a scope inside a scanned, checkpointed layer under
# value_and_grad gives for the three passes, the scan's own ops, the
# optimizer; a vmap and a doubly wrapped element
PATHS = {
    "jit(step)/jvp(layers)/while/body/closed_call/attention/dot_general":
        ("attention", "forward"),
    "jit(step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
    "rematted_computation/attention/dot_general": ("attention", "replay"),
    "jit(step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
    "attention/dot_general": ("attention", "backward"),
    "jit(step)/jvp(layers)/while/body/dynamic_update_slice":
        ("layer_loop", "forward"),
    "jit(step)/optimizer/mul": ("optimizer", "none"),
    # the primitive a path may end in is not the wrapper of the backward
    "jit(_step)/jvp(layers)/while/body/closed_call/attention/transpose:":
        ("attention", "forward"),
    "jit(_step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
    "rematted_computation/attention/transpose": ("attention", "replay"),
    "jit(_step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
    "attention/transpose": ("attention", "backward"),
    "jit(step)/jvp(mtp)/embed/gather": ("embed", "forward"),
    # made by the compiler inside the body, named after the loop itself
    "jit(step)/transpose(jvp(layers))/while:": ("unscoped", "backward"),
    "jit(step)/jvp(layers)/broadcast_in_dim": ("layer_loop", "forward"),
    "jit(step)/jvp(head_loss)/vmap(router)/reduce_sum:":
        ("head_loss", "forward"),
    "jit(step)/transpose(jvp(vmap(feed_forward)))/experts/gmm.pallas/"
    "jit(gmm)/pallas_call": ("feed_forward", "backward"),
    "jit(<lambda>)/random_bits": ("unscoped", "none"),
    "": ("unscoped", "none"),
}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_bucket_and_pass_of_a_path(path):
    parts = sc.elements(path)
    assert (sc.bucket(parts), sc.which_pass(parts)) == PATHS[path]


def test_elements_unwrap_and_scopes_inside():
    assert sc.elements("jit(step)/transpose(jvp(layers))/while/body/"
                       "attention/mul:") == [
        "jit(step)", "transpose(", "jvp(", "layers", "while", "body",
        "attention", "mul"]
    # a fusion that names two instructions: the first
    assert sc.elements("jit(s)/jvp(embed)/mul;jit(s)/optimizer/add") == [
        "jit(s)", "jvp(", "embed", "mul"]
    assert sc.elements(None) == []
    parts = sc.elements("jit(step)/transpose(jvp(vmap(feed_forward)))/"
                        "combine/experts/gmm.pallas/jit(gmm)/pallas_call")
    assert parts[1:5] == ["transpose(", "jvp(", "vmap(", "feed_forward"]
    assert sc.sub_scope(parts) == "experts"            # the innermost
    assert sc.kernel_scope(parts) == "gmm.pallas"
    assert sc.kernel_scope(sc.elements(
        "jit(s)/jvp(layers)/attention/flash.dkdv.resident/pallas_call")) \
        == "flash.dkdv.resident"
    assert sc.kernel_scope(["flash.fwd_plan", "gmm"]) is None
    assert sc.sub_scope(["feed_forward", "mul"]) is None
    assert sc.sub_scope(["optimizer", "rule", "add"],
                        sc.OPTIMIZER_SCOPES) == "rule"


def test_wire_reader_on_the_unscoped_trace():
    labels = sc.read_file(TINY)
    (line,) = [n for n in labels if n.startswith("%convolution_tanh_fusion.2")]
    assert labels[line] == {
        "tf_op": "jit(tiny_program)/while/body/closed_call/dot_general:",
        "hlo_category": "convolution fusion"}
    # every op the reduction names is a key: the metadata's name IS the
    # event's name
    red = tr.reduce_file(TINY)
    assert all(n in labels for n, _ in red["device_ops"])
    assert {v.get("hlo_category") for n, v in labels.items()
            if n.startswith("%copy-start")} == {"copy-start"}
    # a program without scopes reads nothing, through every metric
    assert sc.table(red["device_ops"], labels) is None


def _obs(path):
    red = tr.reduce_file(path)
    red.pop("structure")
    return {"trace": red}


def test_scope_share_reads_nothing_without_scopes(monkeypatch):
    monkeypatch.setattr(sc, "of_run", lambda d=None: sc.read_file(TINY))
    for spec in ({"bucket": "attention"}, {"bucket": "unscoped"},
                 {"pass": "replay"}):
        assert scope_share.read(spec, _obs(TINY)) is None
    assert scope_share.read({"bucket": "attention"}, {"trace": None}) is None
    monkeypatch.setattr(sc, "of_run", lambda d=None: None)    # no trace file
    assert scope_share.read({"bucket": "attention"}, _obs(TINY)) is None


def test_scoped_trace_every_bucket_every_pass(monkeypatch):
    labels = sc.read_file(SCOPED)
    obs = _obs(SCOPED)
    t = obs["trace"]
    assert t["devices"] == 1
    assert all(n in labels for n, _ in t["device_ops"])
    cells = sc.table(t["device_ops"], labels)
    # exclusive, and together the busy time
    assert sum(cells.values()) == pytest.approx(t["busy_s"])
    assert {b for b, _ in cells} == set(sc.BUCKETS)
    assert {p for _, p in cells} == set(sc.PASSES)
    for half in ("attention", "mixer", "feed_forward"):
        for p in ("forward", "replay", "backward"):
            assert cells.get((half, p), 0) > 0, (half, p)
    assert cells[("optimizer", "none")] > 0
    # the custom_vjp's backward rule inherits the half it was called in,
    # and its kernel-call scope stands on its ops
    kernels = {(sc.kernel_scope(p), sc.bucket(p), sc.which_pass(p))
               for _, _, p in sc.labelled(t["device_ops"], labels)}
    assert ("flash.dq.loop", "attention", "backward") in kernels
    assert ("flash.dq.loop", "mixer", "backward") in kernels
    assert ("flash.fwd.loop", "attention", "replay") in kernels
    # the batch's draw program carries no scope of the vocabulary
    assert any("jit(draw)" in (v.get("tf_op") or "") for v in labels.values())

    monkeypatch.setattr(sc, "of_run", lambda d=None: labels)
    shares = {b: scope_share.read({"bucket": b}, obs) for b in sc.BUCKETS}
    assert all(v is not None and v > 0 for v in shares.values()), shares
    assert sum(shares.values()) == pytest.approx(
        100.0 * t["busy_s"] / t["window_s"])
    replay = scope_share.read({"pass": "replay"}, obs)
    assert 0 < replay < shares["attention"] + shares["mixer"] \
        + shares["feed_forward"]
    with pytest.raises(ValueError):
        scope_share.read({"bucket": "attention", "pass": "replay"}, obs)
    with pytest.raises(ValueError):
        scope_share.read({"bucket": "attn"}, obs)


def test_report_prints_the_table(capsys):
    sc.report(SCOPED)
    out = capsys.readouterr().out
    assert "3 executions of jit_step" in out
    for word in sc.BUCKETS + sc.PASSES + ("flash.dq.loop", "jit(draw)"):
        assert word in out, word
    # the optimizer's sub-scopes, each with device time of its own
    (line,) = [ln for ln in out.splitlines() if ln.startswith("optimizer, ")]
    split = dict(re.findall(r"(update|rule|grad_norm) ([0-9.]+)", line))
    assert set(split) == {"update", "rule", "grad_norm"}
    assert all(float(v) > 0 for v in split.values()), line
    # the prediction module's parts, in the model's buckets: its embedding
    # and its head and loss, less than the buckets they are part of
    at, end = out.index("under mtp"), out.index("kernel-call scopes")
    last = lambda lines: {ln.split()[0]: float(ln.split()[-1])  # noqa: E731
                          for ln in lines}
    module = last(out[at:end].splitlines()[1:])
    whole = last(out[:at].splitlines()[2:11])
    assert set(module) == {"embed", "head_loss", "all"}, module
    for b in ("embed", "head_loss"):
        assert 0 < module[b] < whole[b], (b, module, whole)


CELLS = ("train-deepseek7b-l8", "train-deepseek7b-fsdp2tp2",
         "train-olmoe1b7b-s4096-b4", "train-granite4hs-ep8-s8192-b2",
         "train-glm47flash-ep8-s8192-b2")


@pytest.mark.parametrize("cell", CELLS)
def test_the_manifest_lists_every_cell_for_the_scope_metrics(cell):
    """Seven metrics in every cell and ``mixer_device_share`` where a mixer
    runs: each entry resolves to ``scope_share`` with the unit its file
    states, and the buckets' metrics cover ``BUCKETS`` but ``embed``."""
    from benchmark import resolve

    kind = resolve.workload(cell)["kind"]
    per_layer = resolve.metrics_for(cell, "per_layer", kind)
    specs = {m["name"]: resolve.layer_metric(m["name"]) for m in per_layer}
    mine = {n: s for n, s in specs.items() if s["reader"] == "scope_share"}
    buckets = {s["bucket"] for s in mine.values() if "bucket" in s}
    assert buckets == set(sc.BUCKETS) - {"embed"} - (
        set() if kind == "train_hybrid" else {"mixer"})
    assert [s["pass"] for s in mine.values() if "pass" in s] == ["replay"]
    for m in per_layer:
        if m["name"] in mine:
            assert m["name"] == (mine[m["name"]].get("bucket", "remat_replay")
                                 + "_device_share")
            assert (m["unit"], m["moves"], m["source"], m["better"]) == (
                mine[m["name"]]["unit"], "train_tok_s_chip", "device_trace",
                "lower")
            resolve.reader("scope_share")
