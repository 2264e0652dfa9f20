"""The harness finds everything by name and refuses what it does not
know; every entry of BENCHMARK.json resolves to files that are there."""
import pytest

from benchmark import model, resolve
from benchmark.readers import kernel_roofline


def test_finds_cell_config_mix_and_metric_by_name():
    cell = resolve.cell("train-deepseek7b-l8")
    assert cell["kind"] == "train" and cell["chips"] == 1
    assert cell["config"]["hidden_size"] == 4096
    assert cell["mix"]["seq"] == 4096
    assert resolve.kind("train").run and resolve.kind("serve").run
    spec = resolve.layer_metric("device_idle_share.train")
    assert resolve.reader(spec["reader"]).read(spec, {}) is None


@pytest.mark.parametrize("call,name", [
    (resolve.cell, "no-such-cell"), (resolve.config, "no-such-config"),
    (resolve.traffic, "no-such-mix"), (resolve.layer_metric, "no_such"),
    (resolve.kind, "nosuchkind"), (resolve.reader, "nosuchreader"),
    (resolve.peak, "TPU v9 imaginary"), (resolve.config, "../peaks"),
])
def test_refuses_an_unknown_name(call, name):
    with pytest.raises(resolve.UnknownName):
        call(name)


def test_every_manifest_entry_resolves():
    man = resolve.manifest()
    configs = {c["name"]: c for c in man["configs"]}
    e2e = {m["name"] for m in man["end_to_end"]}
    for w in man["workloads"]:
        cell = resolve.cell(w["name"])
        assert (cell["config_name"], cell["mix_name"], cell["chips"]) == (
            w["config"], w["traffic"], w["chips"])
        entry = configs[w["config"]]
        assert entry["file"] == f"benchmark/configs/{w['config']}.json"
        assert entry["reduced"] == cell["config"]["reduced"]
        assert entry["source"] == cell["config"]["source"]
        model.sizes(cell["config"])
        for m in resolve.metrics_for(w["name"], "per_layer", cell["kind"]):
            spec = resolve.layer_metric(m["name"])
            assert cell["kind"] in spec["kinds"]
            assert m["moves"] in e2e
            resolve.reader(spec["reader"])


def test_published_widths_are_unchanged():
    full, cut = resolve.config("deepseek-llm-7b"), resolve.config(
        "deepseek-llm-7b-l8")
    for k, v in full.items():
        if k not in ("num_hidden_layers", "reduced", "stands_for", "cut",
                     "memory_plan"):
            assert cut[k] == v, k
    assert (cut["num_hidden_layers"], full["num_hidden_layers"]) == (8, 30)
    m = model.sizes(resolve.config("mistral-7b-v0.3-l16"))
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_ff"],
            m["vocab_size"], m["n_layers"]) == (4096, 32, 8, 14336, 32768, 16)


def test_pallas_calls_are_told_by_signature():
    fwd = ('%closed_call.9 = (bf16[3,32,4096,128]{3,2,1,0}, f32[3,32,4096,128]'
           '{3,2,1,0}) custom-call(bf16[3,32,4096,128]{3,2,1,0} %q, bf16[3,32,'
           '4096,128]{3,2,1,0} %k, bf16[3,32,4096,128]{3,2,1,0} %v), '
           'custom_call_target="tpu_custom_call", operand_layout=...')
    assert kernel_roofline.signature(fwd) == (2, 3)
    assert kernel_roofline.FLASH[(2, 3)] == "fwd"
    assert kernel_roofline.signature("%fusion.1 = f32[] fusion(%a)") is None


def test_a_foreign_mosaic_call_in_a_train_trace_is_an_error():
    cell = resolve.cell("train-deepseek7b-l8")
    def line(shape):
        return (f"%call.1 = (bf16[{shape}]{{3,2,1,0}}, f32[{shape}]{{3,2,1,0}})"
                f" custom-call(bf16[{shape}]{{3,2,1,0:T(8,128)(2,1)}} %q, "
                f"bf16[{shape}]{{3,2,1,0}} %k, bf16[{shape}]{{3,2,1,0}} %v), "
                'custom_call_target="tpu_custom_call", operand_layout=...')
    def read(name):
        obs = {"trace": {"device_ops": [[name, 0.07]], "op_calls": {name: 1}},
               "peak": resolve.peak("TPU v5 lite"), "cell": cell,
               "sizes": model.sizes(cell["config"])}
        return kernel_roofline.read({"kernel": "flash_attention"}, obs)
    assert kernel_roofline.operand_shapes(line("3,32,4096,128"))[0] == [
        3, 32, 4096, 128]
    assert 0 < read(line("3,32,4096,128")) < 100
    with pytest.raises(ValueError):
        read(line("3,32,2048,128"))       # the signature of flash, not its shapes
    assert read("%fusion.1 = f32[] fusion(%a)") is None
