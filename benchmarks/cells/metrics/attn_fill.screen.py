"""Share of the query-key pairs the attention kernel computes that are
real, in percent: the program counters ``attn.pairs`` (sum of n_g^2
over the graphs of each launch) over ``attn.pairs_computed`` (the pairs
of the key blocks each 128-row query tile visits, from the table the
kernel is given), both counted on the host per launch."""

import bench_spans


def read(view):
    pairs = bench_spans.counter("attn.pairs")
    computed = bench_spans.counter("attn.pairs_computed")
    if not pairs or not computed:
        return None
    return 100.0 * pairs / computed
