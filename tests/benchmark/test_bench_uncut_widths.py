"""The three faults of ``test_bench_manifest._alter`` that are built from a
reduced key and a rope key, held against every architecture: one whose
configuration cuts nothing and has no rope key (the first came with PR 36)
has neither, and ``_alter`` cannot build them there. A configuration with
nothing reduced is first given a cut of its depth that is sound (the public
value under ``published``), so that the fault alone is what the check
refuses."""

import json

import pytest

import manifest as M
import published_widths
from test_bench_manifest import _architectures

FAULTS = ["reduced_key_lacks_its_public_value", "reduced_key_states_another_public_value",
          "reduced_names_a_key_that_is_no_cut_of_scale"]
ARCHITECTURES = {cfg.get("model_type", "toy"): (entry, cfg, public)
                 for entry, cfg, public in _architectures(M.load_manifest())}


def _cut_depth_soundly(cfg: dict, entry: dict, public: dict):
    depth = public["roles"]["depth"]
    for e in (cfg, entry):
        e["reduced"] = [depth]
    cfg.setdefault("published", {})[depth] = public["values"][depth]


def _no_cut_of_scale(public: dict) -> str:
    """A public key that is neither depth, experts nor vocabulary, nor a width."""
    roles, values = public["roles"], public["values"]
    scale = {roles.get(r) for r in ("depth", "experts", "vocabulary")}
    return next(k for k in values if k not in scale and not published_widths.WIDTH.search(k))


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("model_type", sorted(ARCHITECTURES))
def test_a_fault_in_what_is_reduced_fails_the_published_widths(model_type, fault):
    entry, cfg, public = ARCHITECTURES[model_type]
    cfg, entry = json.loads(json.dumps(cfg)), dict(entry)
    if not entry["reduced"]:
        _cut_depth_soundly(cfg, entry, public)
    published_widths.check(cfg, entry, public)  # sound before the fault
    key = entry["reduced"][0]
    if fault == "reduced_key_lacks_its_public_value":
        del cfg["published"][key]
    elif fault == "reduced_key_states_another_public_value":
        cfg["published"][key] += 1
    else:
        other = _no_cut_of_scale(public)
        for e in (cfg, entry):
            e["reduced"] = e["reduced"] + [other]
        cfg["published"][other] = public["values"][other]
    with pytest.raises(AssertionError):
        published_widths.check(cfg, entry, public)

