"""Operations and bytes of ``granite-4.0-h-micro`` from shapes, as ``counts.py``
has them for the Mistral decoder: what the algorithm needs, not what some
implementation runs. ``s`` is ``dict(reference_granite.spec(config))``. A
multiply-add is two operations; a backward pass is twice its forward.

The state-space scan is counted in its CHUNKED form at the published chunk
(``mamba_chunk_size``), the form the published implementation and every fast
one computes, for the elements its causal mask keeps: a chunk of ``L`` tokens
has ``L (L + 1) / 2`` (query, key) pairs. Per pair, ``C_i . B_j`` once a group
(``2 N``) and, a head, the decay's multiply and the product with ``dt x`` (``1
+ 2 P``); per token and head, the state handed on and the carried state's
part of ``y`` (``2 P N`` each) and ``D x`` (``2 P``). Its bytes are one read of
``x``, ``dt``, ``B``, ``C`` and one write of ``y`` forward, and backward one
read of those and of ``dy`` and one write of the four gradients: both fixed by
the configuration, not by who implements the scan. Recomputation (the cell
trains with ``remat``) counts nothing, so a share of a roofline or of a peak
built on this can only read low.
"""

from __future__ import annotations

from benchmark import counts


def layers_of(s: dict, kind: str) -> int:
    return sum(k == kind for k in s["layers"])


def param_count(s: dict) -> int:
    """Every parameter held: the mixers and attention with the heads held, the MLPs, norms, the tied embedding."""
    d, f = s["d"], s["f"]
    d_inner, gn = s["mh"] * s["mp"], s["g"] * s["n"]
    mixer = d * (2 * d_inner + 2 * gn + s["mh"]) + (s["taps"] + 1) * (d_inner + 2 * gn) + 3 * s["mh"] + d_inner + d_inner * d
    attn = 2 * d * s["h"] * s["hd"] + 2 * d * s["kh"] * s["hd"]
    every = 3 * d * f + 2 * d  # the MLP and the block's two norms
    return layers_of(s, "mamba") * (mixer + every) + layers_of(s, "attention") * (attn + every) + s["v"] * d + d


def forward_flops_per_token(s: dict) -> dict:
    """One token's forward through everything but attention's scores and the scan, by part."""
    d = s["d"]
    d_inner, gn = s["mh"] * s["mp"], s["g"] * s["n"]
    return {
        "ssm_proj": layers_of(s, "mamba") * (2 * d * (2 * d_inner + 2 * gn + s["mh"]) + 2 * d_inner * d),
        "ssm_conv": layers_of(s, "mamba") * 2 * s["taps"] * (d_inner + 2 * gn),
        "attn_proj": layers_of(s, "attention") * (4 * d * s["h"] * s["hd"] + 4 * d * s["kh"] * s["hd"]),
        "mlp": len(s["layers"]) * 6 * d * s["f"],
        "head": 2 * d * s["v"],
    }


def scan_flops(s: dict, batch: int, seq: int) -> int:
    """One mamba layer's scan, forward, in the chunked form at the published chunk."""
    chunk = min(s["chunk"], seq)
    kept = (seq // chunk) * chunk * (chunk + 1) // 2
    per_pair = 2 * s["n"] * s["g"] + s["mh"] * (1 + 2 * s["mp"])
    per_token = s["mh"] * (4 * s["mp"] * s["n"] + 2 * s["mp"])
    return batch * (kept * per_pair + seq * per_token)


def scan_bytes(s: dict, batch: int, seq: int, itemsize: int = 2) -> int:
    """One mamba layer's scan, forward and backward: ``x``, ``B``, ``C``, ``y`` and their gradients in the compute
    dtype, ``dt`` and its gradient in float32."""
    d_inner, gn = s["mh"] * s["mp"], s["g"] * s["n"]
    forward = itemsize * (2 * d_inner + 2 * gn) + 4 * s["mh"]
    backward = itemsize * (3 * d_inner + 4 * gn) + 8 * s["mh"]
    return batch * seq * (forward + backward)


def attention_flops(s: dict, batch: int, seq: int) -> int:
    """QK^T and PV, forward, of the attention layers: no window."""
    return layers_of(s, "attention") * batch * 4 * s["h"] * s["hd"] * counts.keys_attended_sum(0, seq, None)


def train_flops_per_step(s: dict, batch: int, seq: int) -> int:
    """Forward and backward of one step."""
    dense = batch * seq * sum(forward_flops_per_token(s).values())
    return 3 * (dense + attention_flops(s, batch, seq) + layers_of(s, "mamba") * scan_flops(s, batch, seq))


def scan_flops_per_step(s: dict, batch: int, seq: int) -> int:
    return 3 * layers_of(s, "mamba") * scan_flops(s, batch, seq)


def scan_bytes_per_step(s: dict, batch: int, seq: int, itemsize: int = 2) -> int:
    return layers_of(s, "mamba") * scan_bytes(s, batch, seq, itemsize)


def flash_flops_per_step(s: dict, batch: int, seq: int) -> int:
    """The attention kernels' share of a step: forward two products, backward four, the scores' recomputation not counted."""
    return 3 * attention_flops(s, batch, seq)


def flash_bytes_per_step(s: dict, batch: int, seq: int, itemsize: int = 2) -> int:
    """HBM traffic the three kernels need, as ``counts.flash_bytes_per_step``."""
    q = batch * seq * s["h"] * s["hd"] * itemsize
    kv = batch * seq * s["kh"] * s["hd"] * itemsize
    return layers_of(s, "attention") * ((2 * q + 2 * kv) + (4 * q + 2 * kv) + (3 * q + 4 * kv))
