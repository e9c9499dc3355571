"""How the benchmark hands ``kanformer-100m.json`` to the system under
test: the program's model config at the file's sizes, and the weights in
the program's parameter layout."""

import math

from repro.configs import kanformer_100m


def model_config(cfg):
    lo, hi = cfg["kan_grid_range"]
    model = kanformer_100m.build(
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_kv=cfg["num_key_value_heads"],
        kan_ff=cfg["kan_intermediate_size"], vocab=cfg["vocab_size"],
        G=cfg["kan_grid_size"], P=cfg["kan_spline_order"]).model
    (blk,) = model.unit
    assert (blk.kan_grid.x_min, blk.kan_grid.x_max) == (lo, hi), blk.kan_grid
    assert blk.attn.head_dim == cfg["head_dim"], blk.attn
    assert blk.attn.rope_theta == cfg["rope_theta"], blk.attn
    assert not blk.attn.qkv_bias
    assert math.isclose(math.sqrt(model.d_model), cfg["embedding_multiplier"])
    return model


def to_program(w, cfg):
    lw = w["layers"]
    return {
        "embed": {"table": w["embed"]},
        "final_ln": {"scale": w["final_norm"]},
        "unit": [{
            "ln1": {"scale": lw["attn_norm"]},
            "attn": {n: lw[n] for n in ("wq", "wk", "wv", "wo")},
            "ln2": {"scale": lw["ffn_norm"]},
            "kan": {n: lw[n] for n in ("c1", "b1", "c2", "b2")},
        }],
    }
