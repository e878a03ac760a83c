"""Cells cut to a size the CPU runs in seconds (tests only)."""

TINY = {
    "paper_coded_static": {
        "clients": 8, "points_per_client": 24, "d": 16, "q": 64,
        "classes": 3, "train": {"learning_rate": 0.5}},
    "femnist_hier_coded": {
        "clients": 40, "points_per_client": 8, "d": 16, "q": 64,
        "classes": 5, "hier_shards": 2, "sample_fraction": 0.5,
        "encode_block": 8, "train": {"learning_rate": 0.5}},
}
