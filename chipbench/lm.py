"""What the drivers share: the program's model built from a configuration
file, weights from the seed checked against the program's own parameter
tree, and the per-leaf comparison numbers."""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from chipbench import fl_reference as flr
from chipbench import weights


def model_config(config: dict):
    """The program's ModelConfig: the registered arch with every size of
    the file's ``model`` group set as the file states it."""
    from repro.configs import XLSTMConfig, get_config
    fields = dict(config["model"])
    if "xlstm" in fields:
        fields["xlstm"] = XLSTMConfig(**fields["xlstm"])
    cfg = get_config(config["arch"]).replace(**fields)
    for k, v in config["model"].items():
        got = getattr(cfg, k)
        got = dataclasses.asdict(got) if dataclasses.is_dataclass(got) else got
        if got != v:
            raise ValueError(f"{config['name']}: {k} is {got!r}, file says {v!r}")
    return cfg


class Model:
    """The program's model and the reference's layout of the same weights."""

    def __init__(self, config: dict):
        from repro.models import build_model
        self.config = config
        self.cfg = model_config(config)
        self.lm = build_model(self.cfg)
        self.ref = flr.reference_module(config["reference"])
        self.layout = self.ref.layout(config["model"])
        self.dtype = config["model"]["dtype"]
        want = jax.tree.map(lambda s: (s.shape, str(s.dtype)),
                            weights.shapes(self.layout, self.dtype))
        got = jax.tree.map(lambda s: (s.shape, str(s.dtype)),
                           self.lm.param_specs())
        if want != got:
            raise ValueError(f"{config['name']}: reference layout differs "
                             f"from the program's parameters")

    def params(self, seed: int):
        return weights.init_params(self.layout, seed, self.dtype)

    @property
    def n_params(self) -> int:
        return sum(int(np.prod(s.shape)) for s in
                   jax.tree.leaves(weights.shapes(self.layout, self.dtype)))

    def layout_shapes(self):
        return [s.shape for s in
                jax.tree.leaves(weights.shapes(self.layout, self.dtype))]

    def leaf_names(self):
        return [jax.tree_util.keystr(p) for p, _ in
                jax.tree_util.tree_flatten_with_path(
                    weights.shapes(self.layout, self.dtype))[0]]


def step_checks(run, names, prog: dict, ref: dict, losses_key: str):
    """The numbers a training-like cell is judged by: the largest relative
    gap of a step's scalar (``losses_key``); the first step's update
    (``delta1``: what the server optimizer is handed, read from its state
    after one step) and the change after the compared steps (``change``),
    each by its worst leaf (``*_gap``) and by its median leaf
    (``*_median_gap``).  Leaves whose reference update is under a
    thousandth of the median leaf's are left out.  The numbers the cell's
    limits name are checked; the rest go to the run's notes.  Returns all
    of them."""
    steps = [flr.rel_gap(a, b) for a, b in zip(prog[losses_key],
                                               ref[losses_key])]
    run.notes[f"{losses_key}_gap_by_step"] = steps
    out = {f"{losses_key}_gap": max(steps)}
    for key in ("delta1", "change"):
        gaps, left_out = flr.leaf_gaps(prog[key], ref[key], ref["delta1"])
        worst = int(np.nanargmax(gaps))
        out[f"{key}_gap"] = float(gaps[worst])
        out[f"{key}_median_gap"] = float(np.nanmedian(gaps))
        run.notes[f"{key}_worst_leaf"] = names[worst]
    run.notes["left_out"] = [names[i] for i in left_out]
    for name, value in out.items():
        if name in run.limits:
            run.check(name, value)
        else:
            run.notes.setdefault("not_compared", {})[name] = value
    return out
