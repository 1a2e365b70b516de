"""Share of the HBM roofline the whole commit reaches, in %: the least time
for the bytes a commit must move (read K slot deltas and the parameters,
write the parameters) at the chip's HBM bandwidth, over the device time
per commit: the traced commit program's device time (its ``XLA
Modules`` events) over its runs.  The secure kernel's integer
hashing runs on the vector unit, whose peak is not in the table, so this
share is of bandwidth alone."""


def read(x: dict):
    t, n = x["trace"]["modules"].get("jit_commit", (0.0, 0))
    if t <= 0 or not n:
        return None
    least = x["commit_bytes"] / x["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (t / n)
