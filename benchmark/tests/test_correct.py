"""What decides ``correct`` in a train cell can tell a wrong model from a
right one: the program's forward against the plain reference on the tiny
configuration passes as it is, and fails with an attention output zeroed,
a layer dropped, a wrong mask, a wrong rotary base or matmul inputs
rounded to 8 bits. (The mean loss alone moves by hundredths or less in
every one of these.)"""
import jax
import jax.numpy as jnp
import pytest

from benchmark import model, resolve
from benchmark.kinds import train
from ray_tpu.models import llama

B, S = 4, 256


def _agreement(dtype: str, wrong: str = ""):
    conf = dict(resolve.config("tiny"),
                run={"dtype": dtype, "param_dtype": dtype})
    sizes = model.sizes(conf)
    cfg = model.llama_config(conf, attn_impl="xla")
    params = llama.init_params(jax.random.PRNGKey(7), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (B, S + 1), 0,
                                cfg.vocab_size, "int32")
    run_params, run_cfg = params, cfg
    layers = params["layers"]
    if wrong == "zeroed wo":
        run_params = dict(params, layers=dict(
            layers, wo=jnp.zeros_like(layers["wo"])))
    elif wrong == "dropped layer":
        run_params = dict(params, layers=jax.tree.map(
            lambda w: w[:1], layers))
        run_cfg = cfg.replace(n_layers=1)
    elif wrong == "wrong mask":
        run_cfg = cfg.replace(sliding_window=16)
    elif wrong == "wrong rotary base":
        run_cfg = cfg.replace(rope_theta=1.0)
    elif wrong == "8-bit matmul inputs":
        run_params = dict(params, layers={
            k: (w.astype(jnp.float8_e4m3fn).astype(w.dtype)
                if w.ndim == 3 else w) for k, w in layers.items()})
    program, _ = train.token_loss_fns(run_cfg, sizes)
    _, reference = train.token_loss_fns(cfg, sizes)
    return train.loss_agreement(program(run_params, tokens),
                                reference(params, tokens))


# limits as a cell sets them: a few times the agreement measured (here on
# the CPU: exact in float32, mean 0.009 and 99.9th percentile 0.037 in bf16)
TOL = {"float32": {"token_mean_abs": 1e-4, "token_p999_abs": 1e-3,
                   "step_loss_abs": 1e-4, "min_descent": 0.01},
       "bfloat16": {"token_mean_abs": 0.03, "token_p999_abs": 0.12,
                    "step_loss_abs": 3e-3, "min_descent": 0.01}}


def _token_checks(a: dict, dtype: str) -> list:
    m = {"agreement": a, "first_loss": a["program_loss"],
         "second_loss": a["ref_loss"] - 1.0,
         "ref_loss_updated": a["ref_loss"] - 1.0}
    return [ok for what, ok in train.loss_checks(m, TOL[dtype]).items()
            if what.startswith("per-token")]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_program_agrees_with_the_reference(dtype):
    a = _agreement(dtype)
    assert all(_token_checks(a, dtype)), a
    assert abs(a["program_loss"] - a["ref_loss"]) \
        <= TOL[dtype]["step_loss_abs"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("wrong", [
    "zeroed wo", "dropped layer", "wrong mask", "wrong rotary base",
    "8-bit matmul inputs"])
def test_a_wrong_model_fails(dtype, wrong):
    a = _agreement(dtype, wrong)
    assert not all(_token_checks(a, dtype)), a
    assert a["token_mean_abs"] > 2 * TOL[dtype]["token_mean_abs"], a


@pytest.mark.parametrize("second,updated,ok", [
    (5.90, 5.90, True),      # fell by 0.1, program and reference agree
    (6.00, 6.00, False),     # the update did not lower the loss
    (6.10, 6.10, False),     # wrong sign
    (5.90, 5.95, False),     # the step's loss is not the reference's
])
def test_the_first_update_must_descend(second, updated, ok):
    a = {"program_loss": 6.0, "ref_loss": 6.0, "token_mean_abs": 0.0,
         "token_p999_abs": 0.0, "token_max_abs": 0.0}
    m = {"agreement": a, "first_loss": 6.0, "second_loss": second,
         "ref_loss_updated": updated}
    assert all(train.loss_checks(m, TOL["bfloat16"]).values()) is ok
