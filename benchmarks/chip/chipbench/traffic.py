"""The one traffic generator: turns a mix file (``traffic/<mix>.json``) and
a seed into the clients' data.

The generators are copies of the repo's ``repro/data/mnist_like.py``
(``generate``, ``heterogeneous_split``, ``sample_round_batches``) and
``repro/data/synthetic.token_stream_heterogeneous``, kept here so that a
change to the program cannot move the yardstick.  Every number they draw
comes from ``--seed``; the same seed gives the same data.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent NumPy stream per use of the seed (any size of int)."""
    return np.random.default_rng((int(seed), stream))


# -- MNIST-like images (copy of repro/data/mnist_like.py) --------------------


def _smooth(img, passes=2):
    for _ in range(passes):
        img = (img + np.roll(img, 1, 0) + np.roll(img, -1, 0)
               + np.roll(img, 1, 1) + np.roll(img, -1, 1)) / 5.0
    return img


def _class_template(rng, size=28):
    img = np.zeros((size, size), np.float32)
    for _ in range(rng.integers(2, 4)):
        x, y = rng.integers(6, size - 6, size=2).astype(float)
        dx, dy = rng.normal(size=2)
        for _ in range(rng.integers(15, 30)):
            xi, yi = int(np.clip(x, 1, size - 2)), int(np.clip(y, 1, size - 2))
            img[xi - 1: xi + 2, yi - 1: yi + 2] += 0.5
            dx, dy = 0.8 * dx + 0.6 * rng.normal(), 0.8 * dy + 0.6 * rng.normal()
            nrm = max(np.hypot(dx, dy), 1e-6)
            x += 1.5 * dx / nrm
            y += 1.5 * dy / nrm
    img = _smooth(img, 2)
    return np.clip(img / max(img.max(), 1e-6), 0, 1)


def mnist_like(n_train: int, rng: np.random.Generator):
    """(x (n, 28, 28, 1) in [0, 1], y (n,) int32), classes balanced."""
    templates = [_class_template(rng) for _ in range(10)]
    per = n_train // 10
    xs, ys = [], []
    for cls in range(10):
        out = np.zeros((per, 28, 28, 1), np.float32)
        shifts = rng.integers(-3, 4, size=(per, 2))
        scales = rng.uniform(0.7, 1.3, size=per)
        for i in range(per):
            img = np.roll(templates[cls], shifts[i], axis=(0, 1)) * scales[i]
            img = img + rng.normal(0, 0.15, size=(28, 28))
            if rng.uniform() < 0.5:
                r = rng.integers(1, 27)
                img[[r, r - 1]] = img[[r - 1, r]]
            out[i, :, :, 0] = np.clip(img, 0, 1)
        xs.append(out)
        ys.append(np.full(per, cls, np.int32))
    x, y = np.concatenate(xs), np.concatenate(ys)
    perm = rng.permutation(len(y))
    return x[perm], y[perm]


def heterogeneous_split(x, y, n_clients: int, rng: np.random.Generator):
    """The paper's Section 4.2 split: half of the images uniformly over the
    clients, the other half by label (label l to client l mod n)."""
    perm = rng.permutation(len(y))
    half = len(y) // 2
    idx = [[] for _ in range(n_clients)]
    for j, i in enumerate(perm[:half]):
        idx[j % n_clients].append(i)
    for i in perm[half:]:
        idx[int(y[i]) % n_clients].append(i)
    return ([x[np.array(ix)] for ix in idx], [y[np.array(ix)] for ix in idx])


def sample_round_batches(cx, cy, tau: int, b: int, rng: np.random.Generator):
    """One round's batches ``{"x": (n, tau, b, 28, 28, 1), "y": (n, tau,
    b)}``, drawn per client with replacement."""
    n = len(cx)
    xs = np.zeros((n, tau, b, 28, 28, 1), np.float32)
    ys = np.zeros((n, tau, b), np.int32)
    for i in range(n):
        idx = rng.integers(0, len(cy[i]), size=(tau, b))
        xs[i] = cx[i][idx]
        ys[i] = cy[i][idx]
    return {"x": xs, "y": ys}


# -- token streams (copy of repro/data/synthetic.py) -------------------------


def token_streams(n_clients: int, seq_len: int, n_seqs: int, bigram_vocab: int,
                  id_space: int, skew: float, rng: np.random.Generator):
    """(n_clients, n_seqs, seq_len) int32: each client walks its own bigram
    chain over ``bigram_vocab`` states, sharpened by ``skew``; the states
    map to distinct ids spread over ``[0, id_space)``."""
    ids = rng.permutation(id_space)[:bigram_vocab].astype(np.int32)
    out = np.zeros((n_clients, n_seqs, seq_len), np.int32)
    for i in range(n_clients):
        logits = rng.normal(size=(bigram_vocab, bigram_vocab)) * skew
        probs = np.exp(logits - logits.max(axis=1, keepdims=True))
        probs /= probs.sum(axis=1, keepdims=True)
        cdf = np.cumsum(probs, axis=1)
        for s in range(n_seqs):
            tok = int(rng.integers(bigram_vocab))
            u = rng.uniform(size=seq_len)
            seq = np.empty(seq_len, np.int32)
            for t in range(seq_len):
                seq[t] = tok
                tok = min(int(np.searchsorted(cdf[tok], u[t])),
                          bigram_vocab - 1)
            out[i, s] = ids[seq]
    return out


def array_round(arrays: dict, tau: int, batch: int, seed: int, r: int):
    """Round ``r``'s batches over per-client arrays ``(n, examples, ...)``
    by the draw ``repro.exec.ArraySupplier`` states: per client and local
    step, ``batch`` rows with replacement, from
    ``np.random.default_rng((seed, r))``.  The harness's own answer to what
    that supplier has to serve."""
    n, n_ex = next(iter(arrays.values())).shape[:2]
    idx = np.random.default_rng((seed, r)).integers(0, n_ex,
                                                    size=(n, tau, batch))
    rows = np.arange(n)[:, None, None]
    return {k: v[rows, idx] for k, v in arrays.items()}


# -- the mix ------------------------------------------------------------------


@dataclass
class ClientData:
    """What one run's clients hold: per-client arrays (lists of them for
    images, whose clients hold different counts), tau and the batch."""

    n_clients: int
    arrays: dict
    tau: int
    batch: int

    def sample_round(self, rng: np.random.Generator):
        return sample_round_batches(self.arrays["x"], self.arrays["y"],
                                    self.tau, self.batch, rng)


def make(traffic: dict, config: dict, seed: int) -> ClientData:
    """The clients' data of ``traffic`` for ``config``, from ``seed``."""
    data = traffic["data"]
    n = int(traffic["clients"])
    tr = config["training"]
    rng = rng_for(seed, 1)
    if data["kind"] == "mnist_like":
        x, y = mnist_like(n * int(data["images_per_client"]), rng)
        cx, cy = heterogeneous_split(x, y, n, rng)
        return ClientData(n, {"x": cx, "y": cy}, tr["tau"], tr["batch"])
    if data["kind"] == "token_streams":
        toks = token_streams(n, int(data["seq_len"]),
                             int(data["seqs_per_client"]),
                             int(data["bigram_vocab"]),
                             int(config["vocab_size"]), float(data["skew"]),
                             rng)
        return ClientData(n, {"tokens": toks}, tr["tau"], tr["batch"])
    raise ValueError(f"traffic {traffic['name']!r}: unknown data kind "
                     f"{data['kind']!r}")
