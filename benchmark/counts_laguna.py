"""Operations and bytes of ``laguna-s-2.1`` from shapes, as ``counts.py`` has
them for the Mistral decoder: what the algorithm needs, not what some
implementation runs. ``s`` is ``dict(reference_laguna.spec(config))``. A
multiply-add is two operations; a backward pass is twice its forward.

Attention is counted for the elements the mask keeps: a ``sliding_attention``
layer's queries read ``sliding_window`` keys at most, a ``full_attention``
layer's all before them, each kind with its own query heads. The expert layer
is counted for the pairs that are LIVE (sent to an expert held here), a number
the program counts itself (``moe/pairs_held``, summed over the expert layers of
a step): dead rows of the static buffers, padding and recomputation count
nothing, so a share of a roofline built on this cannot pass 100 % by construction.
"""

from __future__ import annotations

from benchmark import counts

KINDS = ("full_attention", "sliding_attention")


def layers_of(s: dict, kind: str) -> list:
    return [i for i, k in enumerate(s["layers"]) if k == kind]


def expert_layers(s: dict) -> int:
    return sum(k != "dense" for k in s["ffn"])


def param_count(s: dict) -> int:
    """Every parameter held: attention with its gate, the FFNs with the experts held and the shared expert, norms, embedding, head."""
    d, hd, kh = s["d"], s["hd"], s["kh"]
    attn = sum(2 * d * h * hd + 2 * d * kh * hd + d * h + d for h in s["heads"])
    dense = (len(s["ffn"]) - expert_layers(s)) * (3 * d * s["f"] + d)
    expert = expert_layers(s) * ((s["held"][1] - s["held"][0]) * 3 * d * s["fe"] + d * s["experts"] + 3 * d * s["fs"] + d)
    return attn + dense + expert + 2 * s["v"] * d + d


def forward_flops_per_token(s: dict) -> dict:
    """One token's forward through everything but attention's scores and the routed experts, by part."""
    d, hd, kh = s["d"], s["hd"], s["kh"]
    return {
        "attn_proj": sum(4 * d * h * hd + 4 * d * kh * hd for h in s["heads"]),
        "attn_gate": sum(2 * d * h for h in s["heads"]),
        "dense_ffn": (len(s["ffn"]) - expert_layers(s)) * 6 * d * s["f"],
        "shared_expert": expert_layers(s) * 6 * d * s["fs"],
        "router": expert_layers(s) * 2 * d * s["experts"],
        "head": 2 * d * s["v"],
    }


def pair_flops(s: dict) -> int:
    """One live (token, expert) pair's forward: gate, up and down."""
    return 6 * s["d"] * s["fe"]


def kept_elements(s: dict, kind: str, seq: int) -> int:
    """(query, key) pairs one row's mask keeps in a layer of ``kind``."""
    return counts.keys_attended_sum(0, seq, s["window"] if kind == "sliding_attention" else None)


def attention_flops(s: dict, batch: int, seq: int, kinds=KINDS) -> int:
    """QK^T and PV, forward, of the layers of ``kinds``."""
    return sum(batch * 4 * s["heads"][i] * s["hd"] * kept_elements(s, kind, seq) for kind in kinds for i in layers_of(s, kind))


def train_flops_per_step(s: dict, batch: int, seq: int, pairs_held: float) -> float:
    """Forward and backward of one step with ``pairs_held`` live pairs in all its expert layers."""
    dense = batch * seq * sum(forward_flops_per_token(s).values())
    return 3 * (dense + attention_flops(s, batch, seq) + pairs_held * pair_flops(s))


def flash_flops_per_step(s: dict, batch: int, seq: int, kinds=KINDS) -> int:
    """The attention kernels' share of a step: forward two products, backward four, the scores' recomputation not counted."""
    return 3 * attention_flops(s, batch, seq, kinds)


def flash_bytes_per_step(s: dict, batch: int, seq: int, kinds=KINDS, itemsize: int = 2) -> int:
    """HBM traffic the three kernels need, as ``counts.flash_bytes_per_step``: forward reads q, k, v and writes o; dQ
    reads q, k, v, o, dO and writes dQ; dK/dV reads the same and writes dK, dV. A window layer's kernels still read every
    key and value once."""
    total = 0
    for kind in kinds:
        for i in layers_of(s, kind):
            q = batch * seq * s["heads"][i] * s["hd"] * itemsize
            kv = batch * seq * s["kh"] * s["hd"] * itemsize
            total += (2 * q + 2 * kv) + (4 * q + 2 * kv) + (3 * q + 4 * kv)
    return total


def grouped_flops_per_step(s: dict, pairs_held: float) -> float:
    """The grouped products of a step, forward and backward, for its live pairs."""
    return 3 * pairs_held * pair_flops(s)


def grouped_bytes_per_step(s: dict, pairs_held: float, itemsize: int = 2) -> float:
    """HBM traffic they need, as ``counts_lfm2.grouped_bytes_per_step``: forward, a layer, the held experts' three
    matrices once, the live rows in, gate and up out and read again, their product out and in, the rows out; backward the
    matrices again, the rows, the saved gate and up and both results' gradients, and the three matrices' gradients written."""
    d, fe, layers = s["d"], s["fe"], expert_layers(s)
    weights = (s["held"][1] - s["held"][0]) * 3 * d * fe * itemsize
    rows = pairs_held * itemsize  # one element of every live row, over all layers
    forward = layers * weights + rows * (2 * d + 6 * fe)
    backward = 2 * layers * weights + rows * (3 * d + 10 * fe)
    return forward + backward
