"""A test-only architecture module: layers of two kinds (``layer_types``),
a sliding-window layer and a full one, over the dense decoder's matrices.

The program has one window for every layer, so this module serves the
dense decoder's weights and ModelConfig, and records each call; its count of
attention follows the layer kinds, each layer at its own window. It stands
for an architecture the harness was not written for.
"""
from chipbench import arch, weights, work

dense = arch.load("dense_decoder")
CALLS = []


def make_raw(cfgj, seed):
    CALLS.append("make_raw")
    return dense.make_raw(cfgj, seed)


def program_params(raw, cfgj, quant_format="w4a16_g128"):
    CALLS.append("program_params")
    return dense.program_params(raw, cfgj, quant_format)


def program_config(cfgj, quant_format):
    CALLS.append("program_config")
    return dense.program_config(cfgj, quant_format)


def gemm_call(cfgj, rows):
    return dense.gemm_call(cfgj, rows)


def attn_decode(cfgj, positions):
    flops = nbytes = 0
    for kind in cfgj["layer_types"]:
        w = cfgj["sliding_window"] if kind == "sliding_attention" else 0
        for p in positions:
            f, b = work.attn_row(cfgj["num_attention_heads"],
                                 cfgj["num_key_value_heads"],
                                 cfgj["head_dim"],
                                 min(p + 1, w) if w else p + 1)
            flops += f
            nbytes += b
    return flops, nbytes


def model_flops(cfgj, rows, positions):
    head = 2 * rows * cfgj["hidden_size"] * weights.padded_vocab(cfgj)
    return (gemm_call(cfgj, rows)[0] + attn_decode(cfgj, positions)[0]
            + head)


def reduced(cfgj):
    return dict(dense.reduced(cfgj),
                layer_types=["sliding_attention", "full_attention"])
