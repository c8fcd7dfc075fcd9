"""Graph generators: the skew each generator kind promises."""
from repro.data import graphs


def test_graph_stats_match_kind():
    rows, cols, _ = graphs.generate(graphs.GraphSpec("x", 2048, 2048, 20,
                                                     "power_law", 1.1, 0))
    s = graphs.dataset_stats(rows, cols, (2048, 2048))
    assert s["skew_top10"] > 0.25  # power-law: top rows dominate
    rows, cols, _ = graphs.generate(graphs.GraphSpec("y", 2048, 2048, 20,
                                                     "banded", 1.0, 0))
    s2 = graphs.dataset_stats(rows, cols, (2048, 2048))
    assert s2["skew_top10"] < 0.2  # banded: uniform rows
