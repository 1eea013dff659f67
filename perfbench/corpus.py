"""The seeded corpus and queries: a low-intrinsic-dimension gaussian mixture.

Copied in substance from ``benchmarks/baseline_configs.make_lowrank_corpus``
(the corpus ``chip_smoke.py`` proved the deployments' recall on): latents are
a mixture of gaussians in ``r`` dimensions, embedded by a fixed random
orthonormal ``(r, d)`` map, plus small isotropic ambient noise — embeddings
(kNN-LM keys, passage encoders) have low intrinsic dimension, and an
isotropic d >= 512 mixture is the degenerate case in which no quantizer can
rank neighbours. What differs from the original: every chunk of rows has a
random stream of its own, keyed by (seed, stream, chunk), so chunks can be
made in any order and by several threads while earlier chunks are being
added, and the same seed always gives the same rows; and the mixture has
two levels, clusters of equal size and fewer than the index's centroids,
each made of small sub-clusters (PERF.md, section 4: with more clusters than
centroids the longest inverted list, and with it the padded capacity every
scan pays for, doubled from one seed to the next; with large clusters and
no sub-clusters the PQ shortlist missed the recall bar).
"""

import numpy as np

CORPUS, QUERIES = 1, 2  # stream keys


class LowRankMixture:
    """``chunk(stream, index, n)`` -> (n, d) float32, the same for the same
    (seed, stream, index, n).

    ``latent_clusters`` well-separated clusters (centres 4 sigma apart a
    dimension), each made of ``sub_clusters`` nearer ones (``sub_spread``
    sigma apart), each a unit gaussian. The clusters decide the index's
    layout, the sub-clusters where a query's neighbours are."""

    def __init__(self, seed, dim, latent_dim, latent_clusters, sub_clusters,
                 sub_spread, ambient_sigma=0.05):
        rng = np.random.default_rng([int(seed), 0])
        self.seed = int(seed)
        self.dim = int(dim)
        self.ambient_sigma = float(ambient_sigma)
        self.embed = np.linalg.qr(
            rng.standard_normal((dim, latent_dim)))[0].T.astype(np.float32)
        centers = rng.standard_normal((latent_clusters, 1, latent_dim)) * 4.0
        subs = rng.standard_normal((latent_clusters, sub_clusters, latent_dim))
        self.centers = ((centers + sub_spread * subs)
                        .reshape(-1, latent_dim).astype(np.float32))

    def chunk(self, stream, index, n):
        rng = np.random.default_rng([self.seed, int(stream), int(index)])
        # every sub-cluster gets the same share of every chunk, in an order
        # the stream draws: the work a seed makes (list lengths after
        # k-means, hence the padded list capacity the scans pay for) is then
        # the same for every seed, and only the rows differ
        clusters = self.centers.shape[0]
        which = (rng.permutation(n) + rng.integers(clusters)) % clusters
        z = self.centers[which] + rng.standard_normal(
            (n, self.centers.shape[1]), dtype=np.float32)
        x = z @ self.embed
        x += self.ambient_sigma * rng.standard_normal((n, self.dim), dtype=np.float32)
        return x


def mixture_for(config, seed):
    """The generator a configuration's ``corpus`` block describes."""
    c = config["corpus"]
    if c["kind"] != "lowrank_mixture":
        raise ValueError(f"unknown corpus kind {c['kind']!r}")
    return LowRankMixture(seed, config["index"]["dim"], c["latent_dim"],
                          c["latent_clusters"], c["sub_clusters"], c["sub_spread"],
                          c["ambient_sigma"])
