"""Operations and bytes from shapes: what the algorithm needs, not what some
implementation runs. A PR that replaces a kernel or a fusion is read against
the same count; recomputation and padding count nothing.

``hf`` is a configuration's published keys. A multiply-add is two operations.
"""

from __future__ import annotations


def dims(hf: dict) -> dict:
    d, h, kh = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim") or d // h
    return {"d": d, "h": h, "kh": kh, "hd": hd, "f": hf["intermediate_size"], "v": hf["vocab_size"],
            "window": hf.get("sliding_window")}


def layer_matmul_params(hf: dict) -> int:
    """Weights of one layer that a token is multiplied by: q, k, v, o, gate, up, down."""
    s = dims(hf)
    return s["d"] * s["h"] * s["hd"] * 2 + s["d"] * s["kh"] * s["hd"] * 2 + 3 * s["d"] * s["f"]


def head_params(hf: dict) -> int:
    s = dims(hf)
    return s["d"] * s["v"]


def param_count(hf: dict, num_layers: int) -> int:
    """Every parameter: layers with their two norms, embedding, final norm, untied head."""
    s = dims(hf)
    return num_layers * (layer_matmul_params(hf) + 2 * s["d"]) + s["v"] * s["d"] + s["d"] + head_params(hf)


def keys_attended(position: int, window) -> int:
    """Keys a causal query at ``position`` (from 0) reads: itself and those before, ``window`` at most."""
    n = position + 1
    return n if window is None else min(n, window)


def keys_attended_sum(start: int, count: int, window) -> int:
    """Sum of ``keys_attended`` over positions start .. start+count-1, in closed form."""
    def upto(n):  # positions 0 .. n-1
        if window is None or n <= window:
            return n * (n + 1) // 2
        return window * (window + 1) // 2 + (n - window) * window
    return upto(start + count) - upto(start)


def attention_flops(hf: dict, keys: int) -> int:
    """One layer's QK^T and PV over ``keys`` (query, key) pairs in all."""
    s = dims(hf)
    return 4 * s["h"] * s["hd"] * keys


# ------------------------------------------------------------------ training


def train_flops_per_step(hf: dict, num_layers: int, batch: int, seq: int) -> int:
    """Forward and backward of one step: matrix products with the head at every
    position, attention over the keys the mask keeps; backward twice the forward."""
    s = dims(hf)
    tokens = batch * seq
    dense = 2 * tokens * (num_layers * layer_matmul_params(hf) + head_params(hf))
    attn = num_layers * batch * attention_flops(hf, keys_attended_sum(0, seq, s["window"]))
    return 3 * (dense + attn)


def flash_flops_per_step(hf: dict, num_layers: int, batch: int, seq: int) -> int:
    """The attention kernels' share of a step: forward two products, backward
    four (dV, dP, dQ, dK), the scores' recomputation not counted."""
    s = dims(hf)
    return 3 * num_layers * batch * attention_flops(hf, keys_attended_sum(0, seq, s["window"]))


def flash_bytes_per_step(hf: dict, num_layers: int, batch: int, seq: int, itemsize: int = 2) -> int:
    """HBM traffic the three kernels need: forward reads q, k, v and writes o;
    dQ reads q, k, v, o, dO and writes dQ; dK/dV reads the same and writes dK, dV."""
    s = dims(hf)
    q = batch * seq * s["h"] * s["hd"] * itemsize
    kv = batch * seq * s["kh"] * s["hd"] * itemsize
    fwd = 2 * q + 2 * kv
    dq = 4 * q + 2 * kv
    dkv = 3 * q + 4 * kv
    return num_layers * (fwd + dq + dkv)


# ------------------------------------------------------------------- serving


def serve_token_flops(hf: dict, num_layers: int, position: int, with_head: bool) -> int:
    """One token through the model at ``position`` of its request; the head only
    where a token is sampled from the position."""
    s = dims(hf)
    flops = 2 * num_layers * layer_matmul_params(hf)
    flops += num_layers * attention_flops(hf, keys_attended(position, s["window"]))
    return flops + (2 * head_params(hf) if with_head else 0)


def prefill_call(hf: dict, num_layers: int, fill: int, chunk: int, final: bool, itemsize: int = 2) -> tuple:
    """(operations, bytes) of one prefill chunk of ``chunk`` tokens after ``fill``
    cached ones. Bytes: every weight once, the keys and values the chunk reads
    and writes, the one row of logits where the prompt ends."""
    s = dims(hf)
    keys = keys_attended_sum(fill, chunk, s["window"])
    flops = 2 * chunk * num_layers * layer_matmul_params(hf) + num_layers * attention_flops(hf, keys)
    kv_row = 2 * s["kh"] * s["hd"] * itemsize  # one position's key and value in one layer
    read = keys_attended(fill + chunk - 1, s["window"])
    nbytes = num_layers * layer_matmul_params(hf) * itemsize + num_layers * kv_row * (read + chunk)
    if final:
        flops += 2 * head_params(hf)
        nbytes += head_params(hf) * itemsize + s["v"] * 4
    return flops, nbytes


def decode_call(hf: dict, num_layers: int, fills, itemsize: int = 2) -> tuple:
    """(operations, bytes) of one decode step over rows holding ``fills`` cached
    positions each: every weight once, each row's live keys and values, a row of
    logits a request."""
    s = dims(hf)
    rows = len(fills)
    keys = sum(keys_attended(f, s["window"]) for f in fills)
    flops = rows * (2 * num_layers * layer_matmul_params(hf) + 2 * head_params(hf))
    flops += num_layers * attention_flops(hf, keys)
    kv_row = 2 * s["kh"] * s["hd"] * itemsize
    nbytes = (num_layers * layer_matmul_params(hf) + head_params(hf)) * itemsize
    nbytes += num_layers * kv_row * (keys + rows) + rows * s["v"] * 4
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time one chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
