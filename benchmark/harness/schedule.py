"""Arrival schedules of the open-loop traffic mixes.

Every schedule is a list of offsets in seconds from the window's start,
made from the mix's parameters alone.  The order in which messages take
those offsets is drawn from the seed elsewhere, so every seed offers the
same arrivals and the same sizes, in another order.
"""

from __future__ import annotations

from typing import List


def slot_spread(n: int, slot: int, seconds_per_slot: float,
                offset_s: float, spread_s: float) -> List[float]:
    """``n`` arrivals spaced evenly over ``[offset_s, offset_s + spread_s)``
    of slot ``slot`` (the validator guide's attestation deadline at 1/3 of
    the slot, then the gossip's propagation)."""
    start = slot * seconds_per_slot + offset_s
    return [start + spread_s * k / n for k in range(n)]


def arrivals(shape: dict, n_per_slot: int, slots: int,
             seconds_per_slot: float) -> List[List[float]]:
    """Per slot, the offsets of its ``n_per_slot`` messages, as the mix's
    ``arrivals`` object states them."""
    kind = shape["shape"]
    if kind == "slot_spread":
        return [slot_spread(n_per_slot, s, seconds_per_slot,
                            shape["offset_s"], shape["spread_s"])
                for s in range(slots)]
    raise ValueError(f"unknown arrival shape {kind!r}")
