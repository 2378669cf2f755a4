"""Share of the pair slots the ranking loss computes a tree that hold no
pair of documents: 100 x (1 - rank_pairs / rank_pair_slots). The loss
lays queries out in buckets by size and computes, for each query, its
top `ndcg_truncation` documents against a bucket's width of slots
(`learners/ranking_loss.py`): `rank_pair_slots` is the sum over the
buckets of queries x min(truncation, width) x width, `rank_pairs` the
sum over the queries of min(truncation, size) x size, the pairs whose
delta-NDCG can differ from 0. A layout padded to the longest query reads
about 90 here (98 by all size^2 pairs over queries x longest^2, the parent's
layout). Source: `model.training_profile["device_loop.rank_pairs"]`
and `["device_loop.rank_pair_slots"]` of the window's last job, facts of
the query structure the compiled program was handed. A program without
the counters (an older one) gives nothing. Layer
`learners.ranking_loss`; moves train_rows_trees_per_s."""

META = {
    "layer": "learners.ranking_loss",
    "unit": "%",
    "better": "lower",
    "source": "program_counter",
    "moves": "train_rows_trees_per_s",
    "workloads": ["mslr30k_rank.sweep"]
}

def read(run):
    if not run["jobs"]:
        return None
    profile = run["jobs"][-1]["profile"]
    pairs = profile.get("device_loop.rank_pairs")
    slots = profile.get("device_loop.rank_pair_slots")
    if pairs is None or not slots:
        return None
    return 100.0 * (1.0 - pairs / slots)
