"""Device time of the secure commit kernel per traced commit, in ms: the
events of the Pallas kernel named ``fl_secure_commit`` in the traced
commits, over the commits traced.  A program whose kernel carries another
name reads nothing."""
from chipbench.scopes import kernel_seconds

KERNEL = "fl_secure_commit"


def read(x: dict):
    t = kernel_seconds(x["trace"]["ops"]).get(KERNEL, 0.0)
    n = x.get("traced_commits", 0)
    if t <= 0 or not n:
        return None
    return 1e3 * t / n
