"""The one general generator of serving traffic.  A traffic mix is a
data file of parameters; every seed gets the SAME request sizes in the
SAME order (both from the file's own ``sizes_seed``), with other token
contents.  In a closed loop whose requests outlast the window the order
IS the work — which contexts sit in the slots sets the tick's time — so
an order drawn from the seed made the seed change the work (PERF.md,
Findings)."""

import math

import numpy as np


def request_sizes(traffic):
    """The mix's fixed list of (prompt_len, out_len): ``n_sizes``
    stratified log-uniform quantiles of each range, paired and then
    ordered by permutations drawn from the file's own ``sizes_seed``."""
    n = int(traffic["n_sizes"])

    def quantiles(lo, hi):
        q = (np.arange(n) + 0.5) / n
        return np.rint(np.exp(math.log(lo) + q * (math.log(hi)
                                                  - math.log(lo)))
                       ).astype(int)

    prompts = quantiles(*traffic["prompt_len"])
    outs = quantiles(*traffic["out_len"])
    rng = np.random.default_rng(int(traffic["sizes_seed"]))
    outs = outs[rng.permutation(n)]
    cap = int(traffic["max_len"])
    sizes = [(int(p), int(min(o, cap - p))) for p, o in zip(prompts, outs)]
    return [sizes[i] for i in rng.permutation(n)]


def request_stream(traffic, vocab_size, seed):
    """An endless iterator of (prompt tokens, out_len): the sizes in
    the file's order, round after round; token ids uniform over the
    vocabulary from ``seed``."""
    sizes = request_sizes(traffic)
    rng = np.random.default_rng([int(seed), 1])
    while True:
        for plen, out in sizes:
            yield rng.integers(0, vocab_size, plen).tolist(), out
