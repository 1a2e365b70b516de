"""Model FLOP/s utilization of local training over the measured window:
tokens trained x training FLOPs per token (3 x the forward pass of the
model as built, no recompute) / (window x chips x bf16 peak), in %."""


def read(x: dict):
    if not x.get("tokens"):
        return None
    flops = 3 * x["flops_per_token"] * x["tokens"]
    return 100.0 * flops / (x["window_s"] * x["chips"]
                            * x["peaks"]["bf16_flops"])
