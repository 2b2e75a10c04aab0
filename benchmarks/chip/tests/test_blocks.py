"""The llama block against what the harness gave before it had blocks, and
the look-up of a configuration's block by name."""

import hashlib
import json
from pathlib import Path

import jax
import numpy as np
import pytest

import blocks
import run as R
from blocks import llama

HERE = Path(__file__).resolve().parent


def _smoke_model():
    smoke = json.loads((HERE / "smoke.json").read_text())
    conf = json.loads((HERE.parent / "configs" / "smollm-360m.json").read_text())
    return dict(conf["model"], **smoke["model_overrides"])


# sha256 (first 16 hex digits) of every leaf's bytes for seed 11 at smoke
# size, as the harness made them before the weights moved into the block
WEIGHTS_SEED_11 = {
    "['blocks']['attn']['wk']": "1876cf373278263b",
    "['blocks']['attn']['wo']": "105df75164e220d7",
    "['blocks']['attn']['wq']": "28566b3ebd4d9405",
    "['blocks']['attn']['wv']": "0a4ffdebc54d9c38",
    "['blocks']['ln1']": "4e2f5f32b25758ee",
    "['blocks']['ln2']": "1ae72384db24a625",
    "['blocks']['mlp']['w1']": "b4415cc95cf07845",
    "['blocks']['mlp']['w2']": "34d6694fda2351cb",
    "['blocks']['mlp']['w3']": "f6d71bb0f177b128",
    "['embed']['tok']": "5b48c3a0577abb95",
    "['ln_f']": "d43a4068d9b8b947",
}

# the reference's logits at smoke size, seed 11, for TOKENS, as the harness
# computed them before the reference moved into the block: the first four
# vocabulary entries of every position, and the sums of rows 0, 5 and 11
TOKENS = (np.arange(12) * 37 + 5) % 512
LOGITS_FIRST_4 = [
    [0.1963692158460617, -0.2143888771533966, 0.0786222293972969, -0.277058869600296],
    [0.27689746022224426, -0.1109088733792305, 0.20641621947288513, -0.26499032974243164],
    [0.3389628529548645, -0.32418256998062134, 0.24390020966529846, -0.10696668922901154],
    [0.14465641975402832, 0.1372135579586029, 0.16309429705142975, -0.2502298057079315],
    [0.28932181000709534, 0.17087645828723907, 0.15883280336856842, -0.3293300271034241],
    [0.303547203540802, -0.20558470487594604, 0.03530982881784439, -0.2840283513069153],
    [0.19492480158805847, 0.17704711854457855, 0.2711511254310608, -0.07884237915277481],
    [0.07875403016805649, 0.7474891543388367, 0.06058647111058235, -0.13594242930412292],
    [-0.04259038716554642, 0.5182459354400635, 0.03114240989089012, -0.19489918649196625],
    [0.08423660695552826, 0.15180116891860962, 0.22381100058555603, -0.10818521678447723],
    [-0.07275761663913727, 0.47484782338142395, 0.05922602117061615, -0.003983048722147942],
    [0.08519846946001053, 0.3283461332321167, 0.11573457717895508, -0.22173964977264404],
]
LOGITS_ROW_SUMS = [-5.1660637855529785, -0.13111120462417603, 2.2683820724487305]


def test_llama_weights_are_the_ones_made_before_blocks():
    params = llama.make_weights(_smoke_model(), 11)
    got = {jax.tree_util.keystr(k): hashlib.sha256(np.asarray(v).tobytes()).hexdigest()[:16]
           for k, v in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert got == WEIGHTS_SEED_11


def test_llama_reference_logits_are_the_ones_computed_before_blocks():
    model = _smoke_model()
    params = llama.make_weights(model, 11)
    with jax.default_matmul_precision("highest"):
        lg = np.asarray(jax.jit(
            lambda p, t: llama.logits(p, model, t, lambda a: a))(
                params, TOKENS.astype(np.int32)))
    assert lg.shape == (12, 512)
    np.testing.assert_allclose(lg[:, :4], LOGITS_FIRST_4, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(lg[[0, 5, 11]].sum(-1), LOGITS_ROW_SUMS,
                               rtol=1e-5, atol=1e-5)


def test_every_configuration_names_a_known_block():
    for path in (HERE.parent / "configs").glob("*.json"):
        conf = json.loads(path.read_text())
        assert conf["block"] in blocks.known(), path.name


@pytest.mark.parametrize("name", ["no-such-block", None],
                         ids=["unknown", "missing"])
def test_unknown_block_exits_with_the_known_ones(name):
    conf = json.loads((HERE.parent / "configs" / "smollm-360m.json").read_text())
    if name is None:
        del conf["block"]
    else:
        conf["block"] = name
    cell = {"name": "smoke", "chips": 1, "config": conf, "mix": {}}
    with pytest.raises(SystemExit, match=r"unknown block .*'llama'"):
        R.prepare(cell, 11)
