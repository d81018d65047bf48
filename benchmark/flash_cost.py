"""What the flash attention kernels' work requires, from shapes alone: the
operations and bytes of one call, for the roofline shares
``flash_fwd_roofline`` and ``flash_bwd_roofline``.

A call is one layer's attention over a batch of ``b`` sequences of ``t``
tokens, ``h`` query heads and ``kv`` key-value heads of size ``d``, causal,
and with ``window`` banded. What is counted is what the algorithm needs,
whatever the kernel's tiling: the (query, key) pairs inside the causal
triangle and the band, not the tiles a blockwise kernel visits, which are
more; a kernel that skipped nothing would read a small share here. A
multiply-add is two operations; exponentials, masks and rescaling are not
counted.

- forward: ``q k^T`` and ``p v``: 2 matmuls, ``4 d`` operations a pair and
  query head; reads q, k, v and writes o (the row statistics are 1/d of
  that and left out).
- backward: the scores again, ``dv = p^T do``, ``dp = do v^T``, ``dq = ds
  k``, ``dk = ds^T q``: 5 matmuls, ``10 d`` operations a pair. The two
  kernels compute the scores and ``dp`` once each (7 matmuls run): the two
  more are the kernel's cost, not the algorithm's need. Reads q, k, v, do
  (o only for the row sums, in XLA); writes dq and, per query head, dk and
  dv.
"""

from __future__ import annotations


def causal_pairs(t: int, window=None) -> int:
    """(query, key) pairs of one causal sequence: key not after the query
    and fewer than ``window`` before it."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def forward(*, b: int, t: int, h: int, kv: int, d: int, window=None,
            itemsize: int = 2) -> dict:
    pairs = causal_pairs(t, window)
    return {"flops": 4.0 * b * h * pairs * d,
            "bytes": float(itemsize * b * t * d * (2 * h + 2 * kv))}


def backward(*, b: int, t: int, h: int, kv: int, d: int, window=None,
             itemsize: int = 2) -> dict:
    pairs = causal_pairs(t, window)
    return {"flops": 10.0 * b * h * pairs * d,
            # q, do in; dq, dk, dv (per query head) out; k, v in
            "bytes": float(itemsize * b * t * d * (5 * h + 2 * kv))}


def layer_calls(kwargs: dict, *, batch: int, seq_len: int) -> dict:
    """``{kind: shape kwargs of one call}`` for the two kinds of layer of a
    decoder configuration's kwargs (``models/decoder.py``)."""
    out = {}
    for kind, name in (("full", "full_attention"),
                       ("window", "sliding_attention")):
        heads = [h for h, k in zip(kwargs["heads_per_layer"],
                                   kwargs["layer_types"]) if k == name]
        if heads:
            out[kind] = dict(
                b=batch, t=seq_len, h=heads[0], kv=kwargs["num_kv_heads"],
                d=kwargs["head_dim"],
                window=kwargs["window"] if kind == "window" else None)
    return out


def roofline_share(run, kernels, cost_fn) -> float | None:
    """Percent: the least time the chip could take for the calls of
    ``kernels`` the trace holds (the larger of operations over the bf16
    peak and bytes over the HBM peak, a call) over the time they took."""
    from benchmark import peaks, scopes_lm

    found = scopes_lm.of(run)
    if found is None:
        return None
    c = run.counters
    calls = layer_calls(run.config["kwargs"], batch=c["batch"] // c["chips"],
                        seq_len=c["tokens_per_image"])
    flops_peak = peaks.peak(c["device_kind"], "bf16_flops")
    bytes_peak = peaks.peak(c["device_kind"], "hbm_bytes_per_s")
    least = spent = 0.0
    for kind, shape in calls.items():
        cost = cost_fn(**shape)
        per_call = max(cost["flops"] / flops_peak,
                       cost["bytes"] / bytes_peak)
        cells = [found["kernels"][k][kind] for k in kernels]
        # A backward is two kernels a call: the calls are the first's.
        least += per_call * cells[0]["calls"]
        spent += sum(cell["s"] for cell in cells)
    return 100.0 * least / spent if spent > 0 else None
