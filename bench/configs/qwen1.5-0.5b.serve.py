"""How the benchmark hands ``qwen1.5-0.5b.json`` to the system under test:
the program's model config at the file's sizes and ``rope_theta``, and the
weights in the program's parameter layout.

The program's decoder multiplies the token embedding by ``sqrt(d)``;
Qwen2 does not.  The table it is given is divided by ``sqrt(1024) = 32``
and the final norm's scale multiplied by 32: the same function, and the
same numbers in floating point, since 32 is a power of two.  (The output
head reads the table after the final norm, so the two factors cancel.)
"""

import dataclasses
import math

from repro.configs import qwen1_5_0_5b


def model_config(cfg):
    model = qwen1_5_0_5b.build(
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_kv=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"]).model
    model = dataclasses.replace(model, unit=tuple(
        dataclasses.replace(b, attn=dataclasses.replace(
            b.attn, rope_theta=float(cfg["rope_theta"])))
        for b in model.unit))
    (blk,) = model.unit
    assert blk.attn.head_dim == cfg["head_dim"] and blk.attn.qkv_bias, blk.attn
    return model


def to_program(w, cfg):
    r = math.sqrt(cfg["hidden_size"]) / cfg["embedding_multiplier"]
    assert r == 2.0 ** round(math.log2(r)), "the rescale is exact only for powers of two"
    lw = w["layers"]
    return {
        "embed": {"table": w["embed"] / r},
        "final_ln": {"scale": w["final_norm"] * r},
        "unit": [{
            "ln1": {"scale": lw["attn_norm"]},
            "attn": {n: lw[n] for n in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")},
            "ln2": {"scale": lw["ffn_norm"]},
            "mlp": {"wi": lw["w_up"], "wg": lw["w_gate"], "wo": lw["w_down"]},
        }],
    }
