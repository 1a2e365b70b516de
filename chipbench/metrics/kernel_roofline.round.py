"""Share of the HBM roofline the commit kernels of a sync round reach, in
%: the least time for the bytes their shapes need (each leaf's delta as
float32 blocks, read once and written once, for every client) at the
chip's HBM bandwidth, over those kernels' device time in the traced rounds
(trace events matched by kernel name: the Pallas kernels of a sync
round, ``tpu_custom_call``s, are the per-leaf quantize kernels)."""

NAMES = ('custom_call_target="tpu_custom_call"',)


def read(x: dict):
    ops = x["trace"]["ops"]
    t = sum(s for name, s in ops.items()
            if any(n in name for n in NAMES))
    n = x.get("traced_rounds", 0)
    if t <= 0 or not n:
        return None
    least = n * x["kernel_bytes"]["per_round"] / x["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / t
