"""PTF-like survey nights: a ``[ra, dec, time]`` candidate catalog, one
file per night.

Copied from the program's ``repro.arrayio.generator.make_ptf_files`` so
that a later change there cannot move the yardstick, with one deliberate
change: every size the run's work depends on is fixed by the
configuration. The nights' populations are the Pareto tail's evenly
spaced quantiles (the program draws them); which night holds which
population, which canonical field each pointing images, where the fields
lie in the sky and where each cell lies come from the configuration's
``layout_seed``, as a survey's catalog is fixed. Each field is pointed at
equally often. ``seed`` orders each night's rows in its raw file and
draws the attributes: every seed serves the same cells, so the program
plans, chunks and compiles the same work, in another order on disk.

A survey re-images its canonical fields night after night, so files
overlap heavily in (ra, dec) and cover disjoint time ranges.
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


def shuffled(coords: np.ndarray, rng: np.random.Generator,
             n_attrs: int) -> File:
    """The cells in an order drawn from ``rng``, with float32 attributes
    drawn from it."""
    coords = coords[rng.permutation(coords.shape[0])]
    return coords, rng.normal(18.0, 2.0, (coords.shape[0], n_attrs)).astype(
        np.float32)


def generate(config: dict, seed: int) -> Tuple[List[File], np.ndarray]:
    """The nights as ``(coords, attrs)`` pairs (int64 ``(n, 3)``
    coordinates unique within the night, float32 attributes), and the
    ``(n_canonical_fields, 2)`` field centres the traffic anchors at."""
    n_files = int(config["n_files"])
    fields_per_night = int(config["fields_per_night"])
    n_fields = int(config["n_canonical_fields"])
    layout = np.random.default_rng(int(config["layout_seed"]))
    rng = np.random.default_rng(seed)
    ra_hi, dec_hi, t_hi = (int(x) for x in config["domain_hi"])
    hi = np.array([ra_hi, dec_hi, t_hi])
    # The published heavy tail (Pareto with shape ``skew``, plus 5% of the
    # mean) at evenly spaced quantiles: the same tail for every layout,
    # and no draw of a few giant nights.
    u = (np.arange(n_files) + 0.5) / n_files
    tail = (1.0 - u) ** (-1.0 / float(config["skew"])) - 1.0
    pops = np.maximum((int(config["cells_per_file_mean"])
                       * (tail + 0.05)).astype(np.int64), 16)
    pops = layout.permutation(pops)
    pointings = np.resize(np.arange(n_fields), n_files * fields_per_night)
    pointings = layout.permutation(pointings).reshape(n_files,
                                                      fields_per_night)
    # Field centres stay 3% clear of the ra edges and follow the
    # telescope's declination band, so no field is clipped to a wall.
    centres = np.stack([
        layout.integers(int(0.03 * ra_hi), int(0.97 * ra_hi) + 1, n_fields),
        np.clip(layout.normal(0.55 * dec_hi, 0.12 * dec_hi, n_fields),
                0.05 * dec_hi, 0.95 * dec_hi).astype(np.int64)], axis=1)
    night_len = max(2, t_hi // max(n_files, 1))
    files: List[File] = []
    for i in range(n_files):
        t0 = 1 + i * night_len
        t1 = min(t_hi, t0 + night_len - 1)
        n = max(4, int(pops[i] / fields_per_night))
        parts = []
        for f in pointings[i]:
            c_ra = centres[f, 0] + layout.normal(0, ra_hi * 0.002)
            c_dec = centres[f, 1] + layout.normal(0, dec_hi * 0.002)
            parts.append(np.stack([layout.normal(c_ra, ra_hi * 0.01, n),
                                   layout.normal(c_dec, dec_hi * 0.01, n),
                                   layout.integers(t0, t1 + 1, n)], axis=1))
        coords = np.clip(np.concatenate(parts).round().astype(np.int64),
                         1, hi)
        files.append(shuffled(dedup(coords, hi), rng, 2))
    return files, centres
