"""The Criteo-shaped table maker: fields where the configuration puts them."""

import jax
import numpy as np
from jax.sharding import Mesh

import run as harness

CONFIG = harness.load_json(harness.PERF, "configs", "lr-sparse-1m.json")


def made(seed, rows=20_000):
    maker = harness.load_module("tables", CONFIG["data"]["table"])
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    arrays = maker.make(jax.random.PRNGKey(seed), rows, CONFIG["data"], mesh)
    return {name: np.asarray(a) for name, a in arrays.items()}


def test_integer_fields_keep_their_ids_and_categorical_fields_their_cardinality():
    data = CONFIG["data"]
    counts, cards = data["integer_fields"], data["cardinalities"]
    assert counts == 13 and len(cards) == 26 and data["nnz"] == 39
    arrays = made(7)
    ids, values = arrays["indices"], arrays["values"]
    assert (ids[:, :counts] == np.arange(counts)).all()
    assert (values[:, :counts] >= 0).all() and (values[:, :counts] < 1).all()
    assert (values[:, counts:] == 1.0).all()
    assert ids[:, counts:].min() >= counts and ids.max() < data["dim"]
    for field, card in enumerate(cards):
        distinct = len(np.unique(ids[:, counts + field]))
        assert distinct <= card
        if card <= 30:  # a small field's every category shows in 20,000 rows
            assert distinct == card
    # the widest fields spread over the dimension: nearly every row an id of its own
    assert len(np.unique(ids[:, counts + cards.index(max(cards))])) > 0.98 * len(ids)
    assert set(np.unique(arrays["label"])) == {0.0, 1.0}


def test_the_same_seed_gives_the_same_rows_and_another_seed_others():
    first, again, other = made(11, 2_000), made(11, 2_000), made(12, 2_000)
    assert all((first[name] == again[name]).all() for name in first)
    assert (first["indices"] != other["indices"]).any()
