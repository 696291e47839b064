"""Holds a configuration of the manifest to the public values of its
architecture: ``data/published/<model_type>.json``, copied from the public
``config.json`` that the configuration's ``source`` names."""

import json
import os
import re

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "published")
WIDTH = re.compile(r"_dim$|_rank$|hidden_size|intermediate|head")


def load_public(model_type: str, data_dir: str = DATA) -> dict:
    path = os.path.join(data_dir, f"{model_type}.json")
    assert os.path.isfile(path), f"no public values for model_type {model_type!r}: {path} is missing"
    with open(path) as f:
        return json.load(f)


def check(cfg: dict, entry: dict, public: dict):
    """``cfg`` is the configuration's file, ``entry`` its manifest entry."""
    values, roles, reduced = public["values"], public["roles"], entry["reduced"]
    assert cfg["source"] == entry["source"] == public["source"]
    assert cfg["reduced"] == reduced
    may_reduce = {roles[r] for r in ("depth", "experts", "vocabulary") if roles.get(r)}
    for key in reduced:  # depth, experts held, vocabulary; never a width
        assert not WIDTH.search(key) and key in may_reduce, key
        assert key in values and cfg["published"].get(key) == values[key], key
    for key, value in values.items():
        if key not in reduced:
            assert key in cfg and cfg[key] == value, key
    # the floors of the model-configs guide, where they apply
    after_dense = cfg[roles["depth"]] - roles["leading_dense_layers"]
    assert after_dense >= max(4, roles["layer_period"]), "a whole period and four layers after the dense ones"
    if roles["vocabulary"] in reduced:
        assert 8 * cfg[roles["vocabulary"]] >= values[roles["vocabulary"]], "an eighth of the vocabulary"
    if roles.get("experts") in reduced:
        assert cfg[roles["experts"]] >= 8, "8 routed experts"
