"""Seeded structures shaped like OC20 S2EF: periodic slab-plus-adsorbate cells.

One generator feeds every cell of the benchmark. It touches numpy and the
program's own neighbour search (``graphs/radius.radius_graph_pbc``, so the
graphs a cell trains on are the graphs ``submit_structure`` would build from
the same positions) and never JAX.

What is taken from OC20 S2EF (Chanussot et al. 2021, arXiv:2010.09990): the
kind of structure (a metal slab periodic in x and y, vacuum along z, a small
adsorbate on top), the atom-count range 7-225, a 6 A cutoff capped at 50
neighbours. What is ASSUMED, because there is no network here: the size law
(log-normal, clipped, mean near 75), the lattice spacings and the species.
Energies and forces are not DFT: they come from a closed-form Lennard-Jones
pair potential over all pairs inside the cutoff, as
``examples/LennardJones/lj_data.py`` does, so that force labels are the exact
gradient of the energy labels and a training loss can fall.
"""
from __future__ import annotations

import hashlib
import json
import os
from typing import Dict, List

import numpy as np

from hydragnn_tpu.graphs.batch import GraphSample
from hydragnn_tpu.graphs.radius import radius_graph_pbc

GENERATOR_VERSION = 1

# slab metals and adsorbate elements by atomic number; the LJ length scale
# of a slab atom follows the cell's own lattice spacing, an adsorbate's is
# its covalent size
_SLAB_Z = np.array([13, 22, 26, 28, 29, 45, 46, 47, 78, 79])
_ADS_Z = np.array([1, 6, 7, 8])
_ADS_SIGMA = {1: 0.9, 6: 1.25, 7: 1.2, 8: 1.15}


def sample_sizes(rng: np.random.RandomState, count: int, params: Dict
                 ) -> np.ndarray:
    """Atom counts: log-normal, clipped to [min_atoms, max_atoms]."""
    raw = np.exp(rng.normal(np.log(params["size_median"]),
                            params["size_sigma"], size=count))
    return np.clip(np.rint(raw), params["min_atoms"],
                   params["max_atoms"]).astype(int)


def make_structure(rng: np.random.RandomState, n_atoms: int, params: Dict):
    """(atomic numbers [n], positions [n, 3], cell [3, 3], sigma [n])."""
    n_ads = int(rng.randint(1, min(8, n_atoms - 5) + 1))
    n_slab = n_atoms - n_ads
    spacing = rng.uniform(*params["spacing"])
    layers = int(rng.randint(2, 6))
    layers = max(1, min(layers, n_slab // 3))
    per_layer = -(-n_slab // layers)
    nx = max(1, int(round(np.sqrt(per_layer))))
    ny = -(-per_layer // nx)
    skew = rng.uniform(0.0, 0.3)
    cell = np.array([[nx * spacing, 0.0, 0.0],
                     [ny * spacing * skew, ny * spacing, 0.0],
                     [0.0, 0.0, layers * spacing + rng.uniform(
                         *params["vacuum"])]])
    ix, iy, iz = np.meshgrid(np.arange(nx), np.arange(ny),
                             np.arange(layers), indexing="ij")
    # alternate layers sit over the hollows of the one below (ABAB stacking)
    off = 0.5 * (iz % 2)
    frac = np.stack([(ix + off + 0.25) / nx, (iy + off + 0.25) / ny,
                     (iz + 0.5) * spacing / cell[2, 2]], axis=-1
                    ).reshape(-1, 3)
    top = frac[:, 2] >= frac[:, 2].max() - 1e-9
    # vacancies go into the top layer first, so the slab stays compact
    surplus = len(frac) - n_slab
    drop = rng.permutation(np.nonzero(top)[0])[:surplus]
    if len(drop) < surplus:
        rest = np.setdiff1d(np.arange(len(frac)), drop)
        drop = np.concatenate([drop, rng.permutation(rest)[
            :surplus - len(drop)]])
    keep = np.setdiff1d(np.arange(len(frac)), drop)
    slab = frac[keep] @ cell + rng.normal(0.0, params["jitter"],
                                          (n_slab, 3))
    kinds = rng.choice(_SLAB_Z, size=int(rng.randint(1, 4)), replace=False)
    z_slab = rng.choice(kinds, size=n_slab)
    # adsorbate: a bent chain that starts above one top site
    anchor = slab[np.argmax(slab[:, 2])] + np.array(
        [0.0, 0.0, rng.uniform(1.7, 2.2)])
    ads = [anchor]
    for _ in range(n_ads - 1):
        for _try in range(32):
            step = rng.normal(0.0, 1.0, 3)
            step[2] = abs(step[2]) * 0.7 + 0.3
            # bond lengths differ: two equal bonds to atoms of one element
            # would tie PNA's min/max exactly, where its gradient is a
            # matter of convention and no longer comparable
            nxt = ads[-1] + rng.uniform(1.15, 1.4) * step / np.linalg.norm(
                step)
            # self-avoiding: no two adsorbate atoms closer than a bond
            if min(np.linalg.norm(nxt - a) for a in ads) >= 1.1:
                break
        ads.append(nxt)
    z_ads = rng.choice(_ADS_Z, size=n_ads)
    z = np.concatenate([z_slab, z_ads])
    pos = np.concatenate([slab, np.asarray(ads)])
    sigma = np.concatenate([np.full(n_slab, 0.89 * spacing),
                            [_ADS_SIGMA[int(a)] for a in z_ads]])
    return z, pos, cell, sigma


def lj_labels(pos, cell, sigma, cutoff: float):
    """Total energy and forces of a Lennard-Jones mixture (Lorentz rule
    for sigma, unit epsilon) over every periodic pair inside the cutoff."""
    send, recv, shifts = radius_graph_pbc(pos, cell, cutoff)
    disp = pos[send] + shifts - pos[recv]
    r2 = np.maximum(np.sum(disp * disp, axis=1), 1e-12)
    s2 = (0.5 * (sigma[send] + sigma[recv])) ** 2
    inv6 = (s2 / r2) ** 3
    inv12 = inv6 * inv6
    energy = 0.5 * float(np.sum(4.0 * (inv12 - inv6)))
    coef = 4.0 * (12.0 * inv12 - 6.0 * inv6) / r2
    forces = np.zeros_like(pos)
    np.add.at(forces, recv, -coef[:, None] * disp)
    return energy, forces


def generate(count: int, seed: int, params: Dict) -> List[GraphSample]:
    """`count` labelled GraphSamples from `seed`. Energies have the pool's
    mean per-atom energy removed and share one scale with the forces, so
    forces stay the exact negative gradient of the energies."""
    rng = np.random.RandomState(seed)
    cutoff = float(params["cutoff"])
    raw = []
    for n_atoms in sample_sizes(rng, count, params):
        z, pos, cell, sigma = make_structure(rng, int(n_atoms), params)
        energy, forces = lj_labels(pos, cell, sigma, cutoff)
        send, recv, shifts = radius_graph_pbc(
            pos, cell, cutoff, max_neighbours=int(params["max_neighbours"]))
        raw.append((z, pos, cell, send, recv, shifts, energy, forces))
    sizes = np.array([len(r[0]) for r in raw], np.float64)
    energies = np.array([r[6] for r in raw], np.float64)
    per_atom = float(energies.sum() / sizes.sum())
    scale = float(np.std(energies - per_atom * sizes) + 1e-8)
    return [_sample((z / 100.0).astype(np.float32)[:, None], pos, cell, send,
                    recv, shifts,
                    np.asarray([(energy - per_atom * len(z)) / scale]),
                    forces / scale)
            for z, pos, cell, send, recv, shifts, energy, forces in raw]


def _pack(samples: List[GraphSample]) -> Dict[str, np.ndarray]:
    """The samples as a few flat arrays: atom indices fit a byte, and an
    edge's shift is stored as its three integer image offsets (the
    cartesian shift is offsets @ cell, bit for bit). 4,096 structures are
    14 MB compressed and load in well under a second; a pickle of the samples was
    ~210 MB and took 40 s to load on the machine with the chip."""
    offsets = [np.rint(s.edge_shifts.astype(np.float64)
                       @ np.linalg.inv(s.extras["cell64"])).astype(np.int8)
               for s in samples]
    cat = np.concatenate
    return {
        "num_nodes": np.array([s.num_nodes for s in samples], np.int32),
        "num_edges": np.array([s.num_edges for s in samples], np.int32),
        "x": cat([s.x for s in samples]), "pos": cat([s.pos for s in samples]),
        "forces": cat([s.forces for s in samples]),
        "energy": cat([s.energy for s in samples]),
        "cell64": np.stack([s.extras["cell64"] for s in samples]),
        "senders": cat([s.senders for s in samples]).astype(np.uint8),
        "receivers": cat([s.receivers for s in samples]).astype(np.uint8),
        "offsets": cat(offsets)}


def _unpack(arrays: Dict[str, np.ndarray]) -> List[GraphSample]:
    n_at = np.concatenate([[0], np.cumsum(arrays["num_nodes"])])
    e_at = np.concatenate([[0], np.cumsum(arrays["num_edges"])])
    samples = []
    for i, cell in enumerate(arrays["cell64"]):
        n, e = slice(n_at[i], n_at[i + 1]), slice(e_at[i], e_at[i + 1])
        samples.append(_sample(
            arrays["x"][n], arrays["pos"][n], cell, arrays["senders"][e],
            arrays["receivers"][e],
            (arrays["offsets"][e].astype(np.float64) @ cell
             ).astype(np.float32),
            arrays["energy"][i:i + 1], arrays["forces"][n]))
    return samples


def _sample(x, pos, cell64, senders, receivers, shifts, energy, forces
            ) -> GraphSample:
    return GraphSample(
        x=x, pos=pos, senders=senders, receivers=receivers,
        edge_shifts=shifts, cell=cell64,
        y_node=np.zeros((len(x), 1), np.float32), energy=energy,
        forces=forces, cell64=np.asarray(cell64, np.float64))


def load_or_generate(count: int, seed: int, params: Dict, cache_dir: str
                     ) -> List[GraphSample]:
    """`generate`, kept on disk under `cache_dir` keyed by generator
    version, seed, count and parameters: only the first run of a checkout
    pays for the neighbour searches."""
    assert params["max_atoms"] <= 256, "atom indices are stored in a byte"
    key = hashlib.sha256(json.dumps(
        [GENERATOR_VERSION, count, seed, params], sort_keys=True
    ).encode()).hexdigest()[:16]
    path = os.path.join(cache_dir, f"s2ef_like_{key}.npz")
    if os.path.exists(path):
        with np.load(path) as arrays:
            return _unpack(dict(arrays))
    samples = generate(count, seed, params)
    os.makedirs(cache_dir, exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp.npz"
    np.savez_compressed(tmp, **_pack(samples))
    os.replace(tmp, path)
    return samples
