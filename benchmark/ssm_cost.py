"""What one selective scan requires, from shapes alone: the operations and
bytes of one call, for the roofline shares ``ssm_scan_fwd_roofline`` and
``ssm_scan_bwd_roofline``.

A call is one Mamba layer's scan over ``b`` sequences of ``t`` positions,
``c`` channels and ``n`` states a channel (``ops/ssm.py``): ``s_t = exp(dt_t
A) s_{t-1} + dt_t B_t a_t``, ``m_t = C_t . s_t + D a_t``. What is counted is
what the recurrence needs, whatever computes it: the states never have to
leave the chip's fast memory, so the bytes are the operands' and the
results', in the types the configuration states (``a``, ``B``, ``C``, ``m``
and their gradients in the compute type, ``dt`` and its gradient in
float32); ``A``, ``D`` and their gradients are ``c * n`` numbers and left
out. A chunked scan that writes its states, or an associative scan that
passes over them several times, reads a small share here.

- forward: 7 operations a (position, channel, state): the decay's product
  and ``exp`` (2), the input's two products (2), the state's multiply-add
  (2) and the output's multiply (1, its sum over the states counted with
  it); reads ``a``, ``dt``, ``B``, ``C`` and writes ``m``.
- backward: 14 a (position, channel, state), twice the forward's as a
  matrix multiplication's backward is counted; reads those four and the
  incoming gradient and writes the gradients of the four.

There is no matrix-multiplication form of this recurrence (the decay
differs by channel and by state), so the operations are the vector unit's;
``peaks.py`` has no vector-unit peak and none is invented: the least time
is the larger of bytes over the HBM peak and operations over the bf16
matrix peak, which at these shapes is the bytes' by a factor of 17.
"""

from __future__ import annotations


def forward(*, b: int, t: int, c: int, n: int, itemsize: int = 2) -> dict:
    return {"flops": 7.0 * b * t * c * n,
            # a in, m out; dt in float32; B and C
            "bytes": float(b * t * (2 * c * itemsize + 4 * c
                                    + 2 * n * itemsize))}


def backward(*, b: int, t: int, c: int, n: int, itemsize: int = 2) -> dict:
    return {"flops": 14.0 * b * t * c * n,
            # a, g in, da out; dt in and ddt out in float32; B, C, dB, dC
            "bytes": float(b * t * (3 * c * itemsize + 8 * c
                                    + 4 * n * itemsize))}


def scan_calls(kwargs: dict, *, batch: int, seq_len: int) -> tuple:
    """``(shape kwargs of one call, calls a step)`` for a configuration's
    kwargs (``models/sambay.py``): one call a Mamba layer."""
    layers = sum(1 for kind in kwargs["layer_types"] if kind == "mamba")
    return dict(b=batch, t=seq_len, c=kwargs["d_inner"],
                n=kwargs["d_state"]), layers


def roofline_share(run, direction: str) -> float | None:
    """Percent: the least time the chip could take for the scans of the
    traced steps (``forward`` or ``backward`` above; the larger of
    operations over the bf16 peak and bytes over the HBM peak, a call)
    over the device time of the ops under ``ssm/scan`` in that direction."""
    from benchmark import peaks, scopes_ssm

    found = scopes_ssm.of(run)
    if found is None:
        return None
    spent = found["classes"]["ssm_scan"][f"{direction}_s"]
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
    return 100.0 * least * layers * scopes_ssm.traced_steps(run) / spent
