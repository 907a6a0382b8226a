"""What the recurrent state of a serving window's decode steps needed of the
chip, and the share of the window's model time that need is at the memory's
peak.

A recurrent layer (Gated DeltaNet, Kimi Delta Attention; tpu_dist/nn/
deltanet.py) keeps per slot a ``(heads, Dk, Dv)`` float32 state and its
convolution tail(s), whatever the context.  A decode step's one-token update
must read each busy slot's whole state once and write it once: that is the
mathematics' need, and the program counts it
(``SlotEngine.stats()["state"]["state_bytes"]``: twice a slot's state bytes
for each busy slot of each decode step; free slots are nobody's).  Reading
the state a second time for the output, or a pass to decay it apart from the
pass that updates it, is an implementation's choice and is not counted: what
a kept trace's ``state_update`` scopes take over this share is the update's
distance from its roofline.  No operation count: the update is 7 operations
a number of state, memory bound by a wide margin on any chip.  A prefill
starts from the zero state and writes a slot's state once; that is not in
the counter and not here.

This file has the same arithmetic from the configuration's shapes, for the
tests to hold the program's counter to it, and the share.
"""

from __future__ import annotations


def slot_state_bytes(layers: int, heads: int, k_dim: int, v_dim: int,
                     tail_numbers: int, tail_itemsize: int = 2) -> int:
    """Bytes of whole state ONE slot holds: each recurrent layer's float32
    ``heads x k_dim x v_dim`` and its convolution tails' ``tail_numbers``
    in the cache type."""
    return layers * (heads * k_dim * v_dim * 4 + tail_numbers * tail_itemsize)


def bytes_moved(rows: int, slot_bytes: int) -> int:
    """Bytes ``rows`` busy rows (summed over decode steps) move: each slot's
    whole state read once and written once."""
    return 2 * rows * slot_bytes


def least_seconds(state: dict, peak: dict) -> float:
    """Seconds the chip's memory needs for the state the window's decode
    steps had to read and write."""
    return state["state_bytes"] / peak["hbm_bytes_per_s"]


def need_share(state: dict, seconds: float, peak: dict):
    """Least seconds over the ``seconds`` the serving loop charged its
    prefills and decode steps, in percent; None where the program has no
    such counter, the model keeps no whole state or nothing ran."""
    if not state or not state.get("state_bytes") or not seconds:
        return None
    return 100.0 * least_seconds(state, peak) / seconds
