"""Operations and bytes of ``lfm2-24b-a2b`` from shapes, as ``counts.py`` has
them for the Mistral decoder: what the algorithm needs, not what some
implementation runs. ``s`` is ``dict(reference_lfm2.spec(config))``. A
multiply-add is two operations; a backward pass is twice its forward.

The expert layer is counted for the pairs that are LIVE (sent to an expert
held here), a number the program counts itself (``moe/pairs_held``, summed
over the expert layers of a step): dead rows of the static buffers, padding
and recomputation count nothing, so a share of a roofline built on this cannot
pass 100 % by construction.
"""

from __future__ import annotations

from benchmark import counts


def kinds(s: dict) -> dict:
    """How many layers of each sort: conv / attention operators, dense / expert FFNs."""
    n = len(s["layers"])
    return {"conv": s["layers"].count("conv"), "attention": s["layers"].count("full_attention"),
            "dense": min(s["dense"], n), "expert": n - min(s["dense"], n)}


def param_count(s: dict) -> int:
    """Every parameter held: the operators, the FFNs with the experts held, norms, the tied embedding."""
    d, k = s["d"], kinds(s)
    conv = 3 * d * d + d * d + s["taps"] * d + d
    attn = 2 * d * s["h"] * s["hd"] + 2 * d * s["kh"] * s["hd"] + 2 * s["hd"] + d
    dense = 3 * d * s["f"] + d
    expert = (s["held"][1] - s["held"][0]) * 3 * d * s["fe"] + d * s["experts"] + d
    return k["conv"] * conv + k["attention"] * attn + k["dense"] * dense + k["expert"] * expert + s["v"] * d + d


def forward_flops_per_token(s: dict) -> dict:
    """One token's forward through everything but attention's scores and the experts, by part."""
    d, k = s["d"], kinds(s)
    return {
        "conv_op": k["conv"] * (2 * d * 3 * d + 2 * d * d + 2 * s["taps"] * d + 2 * d),
        "attn_proj": k["attention"] * (4 * d * s["h"] * s["hd"] + 4 * d * s["kh"] * s["hd"]),
        "dense_ffn": k["dense"] * 6 * d * s["f"],
        "router": k["expert"] * 2 * d * s["experts"],
        "head": 2 * d * s["v"],
    }


def pair_flops(s: dict) -> int:
    """One live (token, expert) pair's forward: gate, up and down."""
    return 6 * s["d"] * s["fe"]


def attention_hf(s: dict) -> dict:
    """The keys ``counts.py``'s attention functions read, for these shapes (no window)."""
    return {"hidden_size": s["d"], "num_attention_heads": s["h"], "num_key_value_heads": s["kh"], "head_dim": s["hd"],
            "intermediate_size": s["f"], "vocab_size": s["v"]}


def train_flops_per_step(s: dict, batch: int, seq: int, pairs_held: float) -> float:
    """Forward and backward of one step with ``pairs_held`` live pairs in all its expert layers."""
    dense = batch * seq * sum(forward_flops_per_token(s).values())
    attn = kinds(s)["attention"] * batch * counts.attention_flops(attention_hf(s), counts.keys_attended_sum(0, seq, None))
    return 3 * (dense + attn + pairs_held * pair_flops(s))


def flash_flops_per_step(s: dict, batch: int, seq: int) -> int:
    return counts.flash_flops_per_step(attention_hf(s), kinds(s)["attention"], batch, seq)


def flash_bytes_per_step(s: dict, batch: int, seq: int, itemsize: int = 2) -> int:
    return counts.flash_bytes_per_step(attention_hf(s), kinds(s)["attention"], batch, seq, itemsize)


def grouped_flops_per_step(s: dict, pairs_held: float) -> float:
    """The grouped products of a step, forward and backward, for its live pairs."""
    return 3 * pairs_held * pair_flops(s)


def grouped_bytes_per_step(s: dict, pairs_held: float, itemsize: int = 2) -> float:
    """HBM traffic they need. Forward, a layer: the held experts' three matrices
    once, the live rows in ([P, d]), gate and up out and read again, their
    product out and in ([P, fe] each), the rows out. Backward: the matrices
    again, the rows, the saved gate and up and both results' gradients, and
    the three matrices' gradients written."""
    d, fe, layers = s["d"], s["fe"], kinds(s)["expert"]
    weights = (s["held"][1] - s["held"][0]) * 3 * d * fe * itemsize
    rows = pairs_held * itemsize  # one element of every live row, over all layers
    forward = layers * weights + rows * (2 * d + 6 * fe)
    backward = 2 * layers * weights + rows * (3 * d + 10 * fe)
    return forward + backward
