"""GEO-like 2-D points of interest in equal files.

Copied from the program's ``repro.arrayio.generator.make_geo_files`` so
that a later change there cannot move the yardstick, with one deliberate
change: the cells come from the configuration's ``layout_seed``, so that
every run serves the same cells and the program plans, chunks and
compiles the same work; ``seed`` orders each file's rows and draws the
attributes. Seed points lie on a jittered grid (one per stratum, uniform
inside it), each fanned out into Gaussian clones; all cells are shuffled
and split into equal files, so every file overlaps every region.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

File = Tuple[np.ndarray, np.ndarray]


def dedup(coords: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """A sparse array holds at most one cell per coordinate (each in
    ``[1, hi]``): one of each is kept."""
    key = np.ravel_multi_index(coords.T, hi + 1)
    _, keep = np.unique(key, return_index=True)
    return coords[np.sort(keep)]


def generate(config: dict, seed: int) -> Tuple[List[File], None]:
    """The files as ``(coords, attrs)`` pairs (int64 ``(n, 2)``
    coordinates unique within the file, float32 attributes); GEO has no
    anchors for the traffic."""
    n_files = int(config["n_files"])
    n_seeds = int(config["n_seeds"])
    layout = np.random.default_rng(int(config["layout_seed"]))
    rng = np.random.default_rng(seed)
    lon_hi, lat_hi = (int(x) for x in config["domain_hi"])
    hi = np.array([lon_hi, lat_hi])
    # A grid of about n_seeds strata with the domain's aspect ratio.
    gx = max(1, int(round(np.sqrt(n_seeds * lon_hi / lat_hi))))
    gy = max(1, -(-n_seeds // gx))
    strata = layout.permutation(gx * gy)[:n_seeds]
    u = layout.random((n_seeds, 2))
    seeds = np.stack([(strata % gx + u[:, 0]) * lon_hi / gx,
                      (strata // gx + u[:, 1]) * lat_hi / gy], axis=1)
    seeds = np.floor(seeds).astype(np.int64) + 1
    pts = seeds[:, None, :] + layout.normal(
        0, float(config["sigma"]),
        (n_seeds, int(config["clones_per_seed"]), 2))
    pts = np.concatenate([seeds, pts.reshape(-1, 2)], axis=0)
    coords = np.clip(pts.round().astype(np.int64), 1, hi)
    layout.shuffle(coords, axis=0)
    per = len(coords) // n_files
    files: List[File] = []
    for i in range(n_files):
        c = dedup(coords[i * per:(i + 1) * per if i < n_files - 1 else None],
                  hi)
        c = c[rng.permutation(c.shape[0])]
        files.append((c, rng.normal(0.0, 1.0, (c.shape[0], 1)).astype(
            np.float32)))
    return files, None
