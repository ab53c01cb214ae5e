"""One schedule for every seed of a cell that serves only part of its offer.

`lib/traffic.py` gives every seed the same requests in another order. Where a
cell drains, that is the same work. Where it floods (`drain_limit_s` 0, a
rate over the knee) the window closes on a backlog: it serves the first third
or so of the order, and another order is another SAMPLE of the requests. With
prompts lognormal at sigma 1.0 the served sample's mean prompt ran 1,017-1,208
tokens over twelve seeds and `serve_tok_s` followed it (r -0.85), 4-9% between
the quartiles of six runs (PERF.md section 7 row 33): the dice's, not the
system's.

So this deals the generator's own rows again, by the traffic file's
`shape_seed` and by nothing of `--seed`: the same prompt lengths, the same
counts of new tokens and the same gaps between arrivals, in ONE order, which
is that of a (0, m, 2)-net in base 2 (Sobol's first two coordinates under a
digital shift). Arrival i gets the prompt whose length has the rank of the
net's first coordinate and the count of new tokens with the rank of its
second (the two are drawn independently of each other, so pairing them anew
keeps what the file states). Every aligned run of 2^k arrivals then holds one
prompt from each 2^k-quantile of the lengths, one answer from each
2^k-quantile of the counts and one request from each cell of any grid of 2^k
equal boxes over both: whatever part of the order a window serves, and
however much further a faster program gets, it serves the mix the file
states. The gaps keep a random order (arrivals in a regular order would not be
Poisson's). `--seed` draws the token ids, and the weights.

`lib/traffic.py` is not this PR's to edit, so the rows are dealt here, around
its `build_schedule`, for the time of a run. It goes when a `benchmark` PR
lets the generator deal a flood cell's order itself.
"""

from __future__ import annotations

import contextlib

import numpy as np

from perf.lib import traffic as traffic_lib


def net_ranks(n: int, rng) -> tuple:
    """(a, b): two permutations of range(n), the ranks of the two
    coordinates of the first n points of a digitally shifted (0, m, 2)-net
    in base 2, 2^m >= n."""
    m = max(int(n - 1).bit_length(), 1)
    i = np.arange(1 << m, dtype=np.int64)
    x = np.zeros_like(i)  # van der Corput: the index's bits reversed
    y = np.zeros_like(i)  # Sobol's second: direction numbers of x + 1
    v = 1 << (m - 1)
    for j in range(m):
        bit = (i >> j) & 1
        x ^= bit << (m - 1 - j)
        y ^= bit * v
        v ^= v >> 1
    x ^= int(rng.integers(0, 1 << m))
    y ^= int(rng.integers(0, 1 << m))
    return np.argsort(np.argsort(x[:n])), np.argsort(np.argsort(y[:n]))


def deal(rows: list, shape_seed: int) -> list:
    """`build_schedule`'s rows of one tenant with prompts, counts of new
    tokens and gaps dealt again in the one order of `shape_seed`."""
    if len({r["tenant"] for r in rows}) > 1:
        raise ValueError("a dealt order is one tenant's")
    n = len(rows)
    if n == 0:
        return rows
    rng = np.random.default_rng(
        np.random.SeedSequence([int(shape_seed), 0xDEA1]))
    a, b = net_ranks(n, rng)
    prompts = sorted((r["prompt"] for r in rows), key=len)
    new = sorted(r["max_new"] for r in rows)
    gaps = np.sort(np.diff([0.0] + [r["due_s"] for r in rows]))
    due = np.cumsum(gaps[rng.permutation(n)])
    return [{"rid": k, "due_s": float(due[k]), "prompt": prompts[a[k]],
             "max_new": int(new[b[k]]), "tenant": rows[k]["tenant"]}
            for k in range(n)]


@contextlib.contextmanager
def one_order():
    """While open, `lib/traffic.py build_schedule` deals what it drew."""
    drawn = traffic_lib.build_schedule

    def build_schedule(traffic, **kw):
        return deal(drawn(traffic, **kw), traffic["shape_seed"])

    traffic_lib.build_schedule = build_schedule
    try:
        yield
    finally:
        traffic_lib.build_schedule = drawn
