"""A box shifted by a constant step and visited in a fixed order: the
shape of the paper's §4.1 PTF-2 and GEO workloads (arXiv:1803.06089).

A mix file (``bench/traffic/<mix>.json``) gives, per dimension of the
configuration's domain:

- ``box_frac``: the box's side as a share of the axis;
- ``step_frac``: the shift from one position to the next, as a share of
  the axis;

and for the whole mix:

- ``positions``: how many shifted boxes there are;
- ``order``: ``"cycle"`` visits positions ``1..n, 1..n, ...`` (PTF-2:
  1,2,3,4,1,2,3,4) and ``"there_and_back"`` visits ``1..n, n..1, ...``
  (GEO: 1,2,3,4,5,5,4,3,2,1);
- ``origin``: ``"anchor"`` centres the positions' span on the dataset's
  anchor number ``anchor`` (a canonical field of the sky) in the axes
  the anchors give, ``"layout"`` places the span uniformly in the
  domain from the configuration's ``layout_seed`` (every run sends the
  same boxes);
- ``warmup_cycles`` and ``cycles``: passes over the order before the
  window and in the stream (more than a window completes).

The stream is a list of passes, each one pass over the order: the
window closes at the end of a pass, so that every position weighs the
same in it.

Boxes are ``(lo, hi)`` tuples of inclusive integer bounds inside the
domain ``[1, domain_hi]``, clipped to it as the program's ``_clip_box``
does.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

BoxT = Tuple[Tuple[int, ...], Tuple[int, ...]]


def clip_box(lo: Sequence[int], hi: Sequence[int],
             dom_hi: Sequence[int]) -> BoxT:
    """Clip ``[lo, hi]`` to ``[1, dom_hi]``, never leaving it empty."""
    lo = tuple(int(max(a, 1)) for a in lo)
    hi = tuple(int(min(a, b)) for a, b in zip(hi, dom_hi))
    hi = tuple(max(a, b) for a, b in zip(lo, hi))
    return lo, hi


def order(mix: dict) -> List[int]:
    """One pass of position indices."""
    n = int(mix["positions"])
    if mix["order"] == "cycle":
        return list(range(n))
    if mix["order"] == "there_and_back":
        return list(range(n)) + list(range(n - 1, -1, -1))
    raise ValueError(f"unknown order {mix['order']!r}")


def positions(mix: dict, dom_hi: Sequence[int], layout_seed: int,
              anchors: Optional[np.ndarray]) -> List[BoxT]:
    """The shifted boxes, first to last."""
    dom_hi = [int(h) for h in dom_hi]
    if len(mix["box_frac"]) != len(dom_hi):
        raise ValueError(f"the mix has {len(mix['box_frac'])} axes, the "
                         f"domain {len(dom_hi)}")
    n = int(mix["positions"])
    side = [max(1, int(h * f)) for h, f in zip(dom_hi, mix["box_frac"])]
    step = [int(h * f) for h, f in zip(dom_hi, mix["step_frac"])]
    span = [s + (n - 1) * t for s, t in zip(side, step)]
    rng = np.random.default_rng([int(layout_seed), 1])
    lo0 = [int(rng.integers(1, max(1, h - s + 1) + 1))
           for h, s in zip(dom_hi, span)]
    if mix["origin"] == "anchor":
        centre = np.asarray(anchors[int(mix["anchor"])])
        for d, c in enumerate(centre):
            lo0[d] = int(c) - span[d] // 2
    elif mix["origin"] != "layout":
        raise ValueError(f"unknown origin {mix['origin']!r}")
    out = []
    for k in range(n):
        lo = [a + k * t for a, t in zip(lo0, step)]
        out.append(clip_box(lo, [a + s - 1 for a, s in zip(lo, side)],
                            dom_hi))
    return out


def build(mix: dict, config: dict, seed: int, anchors: Optional[np.ndarray]
          ) -> Tuple[List[BoxT], List[List[BoxT]]]:
    """``(warm-up boxes, stream passes)`` of one run: the same for every
    ``seed``."""
    boxes = positions(mix, config["domain_hi"], config["layout_seed"],
                      anchors)
    one = [boxes[k] for k in order(mix)]
    return one * int(mix["warmup_cycles"]), [one] * int(mix["cycles"])
