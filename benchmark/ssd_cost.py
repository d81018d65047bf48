"""What one chunked state-space scan requires, from shapes alone: the
operations and bytes of one call, for the roofline shares
``ssd_fwd_roofline`` and ``ssd_bwd_roofline``.

A call is one Mamba-2 layer's recurrence over ``b`` sequences of ``t``
positions, ``h`` heads of ``p`` channels and ``n`` states a channel
(``ops/ssd.py``): ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t =
S_t C_t + D x_t``, the decay one number a head and position. What is
counted is what the recurrence needs in its matrix-product form at the
published chunk ``q`` = 256, whatever computes it:

- operations (a multiply-add is two), a chunk: the scores ``C B^T`` over
  the causal half of the chunk's pairs, ``q (q + 1) / 2``, once for all
  heads (``2 n`` a pair); the masked product a head over the same pairs
  (``2 p`` a pair); the chunk's state a head (``2 q p n``) and its use
  (``2 q p n``). A kernel that evaluates whole ``(q, q)`` tiles and masks
  them afterwards does more and is credited for this much, so no
  implementation reads over 100% by skipping masked work. The masks'
  exponentials, ``q (q + 1) / 2`` a head and chunk on the vector unit, are
  not counted: ``peaks.py`` has no vector-unit peak and none is invented.
- bytes: the states never have to leave the chip's fast memory, so the
  bytes are the operands' and the results', in the types the configuration
  states: forward ``x``, ``B``, ``C`` read and ``y`` written in the compute
  type and ``dt`` read in float32; backward those four read, the incoming
  gradient read and the four gradients written (``dt``'s in float32).
  ``A``, ``D`` and their gradients are ``h`` numbers and left out. A scan
  that writes its chunk states, as ``ops/ssd.py`` does for its backward,
  or a ``(q, q)`` tile, reads a smaller share here.
- backward operations: twice the forward's, as a matrix multiplication's
  backward is counted.

At the cell's shapes the two bounds nearly meet: forward 26.1 GFLOP (0.13
ms at the bf16 peak) against 140 MB (0.17 ms at the HBM peak); backward 52.1
GFLOP (0.265 ms) against 214 MB (0.261 ms).
"""

from __future__ import annotations

CHUNK = 256  # the source's mamba_chunk_size


def forward(*, b: int, t: int, h: int, p: int, n: int, q: int = CHUNK,
            itemsize: int = 2) -> dict:
    q = min(q, t)
    pairs = q * (q + 1) // 2
    per_chunk = 2 * pairs * n + h * (2 * pairs * p + 4 * q * p * n)
    return {"flops": float(b * (t // q) * per_chunk),
            # x in, y out; B and C; dt in float32
            "bytes": float(b * t * (2 * h * p * itemsize + 2 * n * itemsize
                                    + 4 * h))}


def backward(*, b: int, t: int, h: int, p: int, n: int, q: int = CHUNK,
             itemsize: int = 2) -> dict:
    return {"flops": 2.0 * forward(b=b, t=t, h=h, p=p, n=n, q=q)["flops"],
            # x, g in, dx out; B, C, dB, dC; dt in and ddt out in float32
            "bytes": float(b * t * (3 * h * p * itemsize + 4 * n * itemsize
                                    + 8 * h))}


def scan_calls(kwargs: dict, *, batch: int, seq_len: int) -> tuple:
    """``(shape kwargs of one call, calls a step)`` for a configuration's
    kwargs (``models/granite.py``): one call a Mamba-2 layer."""
    layers = sum(1 for kind in kwargs["layer_types"] if kind == "mamba")
    return dict(b=batch, t=seq_len, h=kwargs["mamba_n_heads"],
                p=kwargs["mamba_d_head"], n=kwargs["mamba_d_state"]), layers


def roofline_share(run, direction: str) -> float | None:
    """Percent: the least time the chip could take for the scans of the
    traced steps (``forward`` or ``backward`` above; the larger of
    operations over the bf16 peak and bytes over the HBM peak, a call)
    over the device time of the ops under ``ssd/scan`` in that direction."""
    from benchmark import peaks, scopes_ssd

    found = scopes_ssd.of(run)
    if found is None:
        return None
    spent = found["classes"]["ssd_scan"][f"{direction}_s"]
    if spent <= 0:
        return None
    c = run.counters
    shape, layers = scan_calls(run.config["kwargs"],
                               batch=c["batch"] // c["chips"],
                               seq_len=c["tokens_per_image"])
    cost = {"forward": forward, "backward": backward}[direction](**shape)
    least = max(cost["flops"] / peaks.peak(c["device_kind"], "bf16_flops"),
                cost["bytes"] / peaks.peak(c["device_kind"],
                                           "hbm_bytes_per_s"))
    return 100.0 * least * layers * scopes_ssd.traced_steps(run) / spent
