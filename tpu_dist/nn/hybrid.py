"""Token mixers side by side inside one layer."""

from __future__ import annotations

from . import functional as F
from .module import Module

__all__ = ["ParallelMixer"]


class ParallelMixer(Module):
    """Several token mixers on ONE input, their outputs scaled and summed
    (drop-in for a block's attention: ``TransformerBlock(mixer=)``)::

        out = sum_i out_multiplier_i * mixer_i(x * in_multiplier_i)

    Falcon-H1's layer: an attention mixer and a state-space mixer read the
    same normalised input and both join the residual
    (``modeling_falcon_h1.py``, ``FalconH1DecoderLayer``).  Each keyword is
    a branch's name and either its module or ``(module, in_multiplier,
    out_multiplier)``; a multiplier of 1 is no operation.  Every branch is a
    submodule under its own name, so it owns parameters and a slot-cache
    entry at its own path (``block3.attn.ssm``) and its operations carry its
    own scope: :meth:`mixers` hands the branches, in order, to whoever
    walks a model's cache-owning mixers (``TransformerLM._mixers``); the
    composite itself keeps nothing."""

    def __init__(self, **branches):
        super().__init__()
        if not branches:
            raise ValueError("ParallelMixer needs at least one branch")
        self.branches = {}
        for name, branch in branches.items():
            module, *scales = branch if isinstance(branch, tuple) else (
                branch,)
            setattr(self, name, module)
            self.branches[name] = tuple(map(float, scales)) or (1.0, 1.0)

    def mixers(self) -> list:
        """The branches' modules, in order: each owns its cache entry."""
        return [getattr(self, name) for name in self.branches]

    def forward(self, x):
        out = None
        for name, (scale_in, scale_out) in self.branches.items():
            y = F.scaled(getattr(self, name)(F.scaled(x, scale_in)),
                         scale_out)
            out = y if out is None else out + y
        return out

    def __repr__(self):
        return f"ParallelMixer({', '.join(self.branches)})"
