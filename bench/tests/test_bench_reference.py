"""The benchmark's own generators and plain reference, on graphs small
enough to count by hand.  Nothing here imports the program."""
import numpy as np
import pytest

from bench import harness, reference
from bench.graphs.csr import csr_from_edges, relabel


# seed 5, n = 4096: what the copied R-MAT recipe gives (the program's
# repro.graphs.rmat gives the same arrays for the same generator state)
PINNED_RMAT_ENTRIES = 39802
PINNED_RMAT_DEGREES = (85, 60)  # max degree, isolated vertices


def gen(name, params, seed=5):
    mod = harness.load_module(harness.BENCH / "graphs" / f"{name}.py",
                              f"bench_graph_{name}")
    return mod.generate(params, harness.seed_rng(seed, 0))


def undirected(g):
    return g.undirected_edges()


def test_csr_from_edges_cleans_the_edge_list():
    g = csr_from_edges(4, [0, 1, 1, 2, 3, 0], [1, 0, 2, 2, 0, 3])
    assert g.row_offsets.tolist() == [0, 2, 4, 5, 6]
    assert g.col_indices.tolist() == [1, 3, 0, 2, 1, 0]
    assert [a.tolist() for a in undirected(g)] == [[0, 0, 1], [1, 3, 2]]


def test_rmat_is_pinned_at_a_small_size():
    params = {"n": 4096, "avg_degree": 10.0, "abcd": [0.45, 0.15, 0.15, 0.25]}
    g = gen("rmat", params, seed=5)
    again = gen("rmat", params, seed=5)
    assert np.array_equal(g.col_indices, again.col_indices)
    assert (g.n, g.entries) == (4096, PINNED_RMAT_ENTRIES)
    deg = g.degrees
    assert (int(deg.max()), int((deg == 0).sum())) == PINNED_RMAT_DEGREES
    assert not np.array_equal(gen("rmat", params, seed=6).col_indices,
                              g.col_indices)
    # symmetric, no self-loops
    src = np.repeat(np.arange(g.n), deg)
    assert not np.any(src == g.col_indices)
    fwd = set(zip(src.tolist(), g.col_indices.tolist()))
    assert all((b, a) in fwd for a, b in fwd)


def test_relabel_renames_vertices_and_keeps_every_size():
    g = gen("rmat", {"n": 512, "avg_degree": 8.0, "abcd": [0.45, 0.15, 0.15, 0.25]})
    perm = np.random.default_rng(1).permutation(g.n)
    h = relabel(g, perm)
    assert (h.n, h.entries) == (g.n, g.entries)
    assert np.array_equal(h.degrees[perm], g.degrees)
    s, d = undirected(g)
    want = {(min(a, b), max(a, b)) for a, b in zip(perm[s].tolist(), perm[d].tolist())}
    assert set(zip(*[x.tolist() for x in undirected(h)])) == want


def test_a_fixed_draw_with_relabeling_gives_every_seed_one_graph():
    # the same K relabelings of one fixed draw for every seed; the seed
    # only rotates the sequence
    cfg = {"generator": "rmat", "graph_seed": 3, "orders": 4,
           "graph": {"n": 1024, "avg_degree": 10.0, "abcd": [0.45, 0.15, 0.15, 0.25]}}
    a, b = harness.generate_graphs(cfg, 1), harness.generate_graphs(cfg, 2**31 + 2)
    assert len(a) == len(b) == 4
    assert {g.entries for g in a + b} == {a[0].entries}
    assert all(np.array_equal(np.sort(g.degrees), np.sort(a[0].degrees))
               for g in a + b)
    assert len({g.col_indices.tobytes() for g in a}) == 4
    assert [g.col_indices.tobytes() for g in b] == \
        [g.col_indices.tobytes() for g in a[1:] + a[:1]]
    assert all(np.array_equal(x.col_indices, y.col_indices)
               for x, y in zip(harness.generate_graphs(cfg, 1), a))
    # without orders, the draw itself; orders need a fixed draw
    one, = harness.generate_graphs(dict(cfg, orders=0), 1)
    assert one.entries == a[0].entries
    with pytest.raises(ValueError):
        harness.generate_graphs(dict(cfg, graph_seed=None), 1)


def test_rmat_rejects_probabilities_that_do_not_sum_to_one():
    with pytest.raises(ValueError):
        gen("rmat", {"n": 64, "avg_degree": 4, "abcd": [0.5, 0.5, 0.5, 0.5]})


def grid(rows, cols):
    """A 5-point grid in raster order."""
    idx = np.arange(rows * cols).reshape(rows, cols)
    return csr_from_edges(rows * cols,
                          np.concatenate([idx[:, :-1].ravel(), idx[:-1, :].ravel()]),
                          np.concatenate([idx[:, 1:].ravel(), idx[1:, :].ravel()]))


def cycle(n):
    return csr_from_edges(n, np.arange(n), (np.arange(n) + 1) % n)


def path(n):
    return csr_from_edges(n, np.arange(n - 1), np.arange(1, n))


def star(n):
    return csr_from_edges(n, np.zeros(n - 1, int), np.arange(1, n))


def clique(n):
    a, b = np.triu_indices(n, 1)
    return csr_from_edges(n, a, b)


@pytest.mark.parametrize("graph, colors", [
    (path(5), 2), (star(6), 2), (clique(5), 5),
    (grid(3, 3), 2), (cycle(5), 3),
])
def test_greedy_colors_hand_countable_graphs(graph, colors):
    got = reference.greedy_coloring(graph.row_offsets, graph.col_indices)
    assert int(got.max()) == colors
    s, d = undirected(graph)
    assert reference.improper_vertices(graph.n, s, d, got) == 0


def test_greedy_follows_the_order_it_is_given():
    # path 0-1-2-3 colored ends first: 0 and 3 take 1, then 1 and 2 need 2, 3
    g = path(4)
    got = reference.greedy_coloring(g.row_offsets, g.col_indices, [0, 3, 1, 2])
    assert got.tolist() == [1, 2, 3, 1]


def test_improper_vertices_counts_a_planted_conflict():
    g = grid(3, 3)
    s, d = undirected(g)
    good = reference.greedy_coloring(g.row_offsets, g.col_indices)
    bad = good.copy()
    bad[0] = bad[1]  # a corner takes its neighbors' color: 0, 1 and 3
    assert reference.improper_vertices(g.n, s, d, bad) == 3
    bad[8] = 0       # the opposite corner left uncolored
    assert reference.improper_vertices(g.n, s, d, bad) == 4
    assert reference.improper_vertices(g.n, s, d, good[:-1]) == g.n

