"""The yardstick's arithmetic against values worked by hand."""
import pytest

from benchmark import flops, model, resolve

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def sizes(name):
    return model.sizes(resolve.config(name))


@pytest.mark.parametrize("name,layer,matmul,total,seq,per_token", [
    # layer: 4 x 4096^2 (32 KV heads) + 3 x 4096 x 11008
    ("deepseek-llm-7b-l8", 202_375_168, 2_038_431_744, 2_457_931_776,
     4096, 13_035_896_832),
    ("deepseek-llm-7b", 202_375_168, 6_490_685_440, 6_910_365_696,
     4096, 41_964_011_520),
    # layer: 2 x 4096^2 + 2 x 4096 x 1024 (8 KV heads) + 3 x 4096 x 14336
    ("mistral-7b-v0.3-l16", 218_103_808, 3_623_878_656, 3_758_231_552,
     2048, 6 * 3_623_878_656 + 6 * 2048 * 4096 * 16),
])
def test_parameters_and_train_flops(name, layer, matmul, total, seq,
                                    per_token):
    s = sizes(name)
    assert flops.layer_matmul_params(s) == layer
    assert flops.matmul_params(s) == matmul
    assert flops.total_params(s) == total
    assert flops.train_flops_per_token(s, seq) == per_token


def test_flash_calls_of_cell_one():
    s = sizes("deepseek-llm-7b-l8")
    unit = 4096 ** 3 * 3                       # S^2 * H*HD * B, causal
    q = 3 * 4096 * 4096 * 2                    # B*S*H*HD bf16; k the same
    fwd = flops.flash_call(s, 3, 4096, "fwd")
    assert fwd == {"ops": 2.0 * unit, "bytes": 4.0 * q}
    assert flops.flash_call(s, 3, 4096, "dq") == {"ops": 2.0 * unit,
                                                 "bytes": 6.0 * q}
    assert flops.flash_call(s, 3, 4096, "dkdv") == {"ops": 3.0 * unit,
                                                   "bytes": 7.0 * q}
    least = flops.least_seconds(fwd, PEAK)
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx(412_316_860_416 / 197e12)


def test_grouped_kv_reads_fewer_bytes():
    s = sizes("mistral-7b-v0.3-l16")
    q, k = 1 * 2048 * 4096 * 2, 1 * 2048 * 1024 * 2
    assert flops.flash_call(s, 1, 2048, "fwd")["bytes"] == 2 * q + 2 * k


def test_paged_decode_is_memory_bound():
    s = sizes("mistral-7b-v0.3-l16")
    call = flops.paged_decode_call(s, [1000] * 32)
    assert call["bytes"] == 2 * 32000 * 8 * 128 * 2 + 2 * 32 * 4096 * 2
    assert call["ops"] == 4 * 32000 * 4096
    least = flops.least_seconds(call, PEAK)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(131_596_288 / 819e9)
