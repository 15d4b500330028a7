"""Train an EGNN potential on synthetic molecule batches (the GNN
``molecule`` shape at example scale) and verify rotation invariance of
the learned energies.

Run:  PYTHONPATH=src python examples/gnn_molecule.py [--steps 40]
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.data import molecule_batch
from repro.models.gnn import egnn
from repro.models.gnn.graph import from_numpy
from repro.train import loop, optimizer as opt


def make_batch(step, batch=16, n_nodes=8, n_edges=16, d_feat=8):
    raw = molecule_batch(step, batch, n_nodes, n_edges, d_feat, seed=0)
    gb = from_numpy(raw["node_feat"], raw["senders"], raw["receivers"],
                    pos=raw["pos"], graph_id=raw["graph_id"],
                    n_graph=raw["n_graph"])
    # synthetic learnable target: summed pairwise-distance energy
    pos = raw["pos"]
    e = []
    for g in range(raw["n_graph"]):
        p = pos[raw["graph_id"] == g]
        d = np.linalg.norm(p[:, None] - p[None, :], axis=-1)
        e.append(d.sum() / len(p) ** 2)
    target = jnp.asarray(np.asarray(e, np.float32)[:, None])
    return gb, target


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=40)
    args = ap.parse_args()

    cfg = egnn.EGNNConfig(n_layers=3, d_hidden=32, d_in=8)
    params = egnn.init_params(cfg, jax.random.PRNGKey(0))
    loss_fn = egnn.make_loss(cfg)
    ocfg = opt.AdamWConfig(lr=2e-3, warmup_steps=5, total_steps=args.steps,
                           weight_decay=0.0)
    lcfg = loop.LoopConfig(total_steps=args.steps, log_every=5)
    params, _, hist = loop.run(params, loss_fn, make_batch, ocfg, lcfg)
    print("loss trajectory:", [round(h["loss"], 4) for h in hist])
    assert hist[-1]["loss"] < hist[0]["loss"], "no learning progress"

    # rotation invariance of the trained model
    gb, tgt = make_batch(0)
    e1, _, _ = egnn.forward(params, gb, cfg)
    A = np.random.default_rng(7).normal(size=(3, 3))
    Q, R = np.linalg.qr(A)
    Q = (Q * np.sign(np.diag(R))).astype(np.float32)
    if np.linalg.det(Q) < 0:
        Q[:, 0] *= -1
    gb_rot = jax.tree.map(lambda x: x, gb)
    import dataclasses
    gb_rot = dataclasses.replace(gb, pos=gb.pos @ jnp.asarray(Q).T)
    e2, _, _ = egnn.forward(params, gb_rot, cfg)
    err = float(jnp.abs(e1 - e2).max())
    print(f"rotation-invariance max err: {err:.2e}")
    assert err < 1e-3
    print("done.")


if __name__ == "__main__":
    main()
