"""A configuration's ``layout`` reaches the served entry: a tiered page
store serves at a residency that holds a round's pages and its livelock
reaches the caller where it does not; a malformed layout fails at load,
before the index build; a resident configuration calls the entry as it
always did and keeps its index cache key; a cell on four chips serves
through the shard_map stepper over exactly those devices."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))

import index  # noqa: E402
import run  # noqa: E402
from test_bench_run import SEED, bench_dir, spec_of  # noqa: E402,F401

# TINY's index: 512 vectors in 8-vector pages over 2 shards, 32 pages a
# shard; 24 frames hold a round's pages on the CPU, 16 do not
SERVES, LIVELOCKS = 24, 16


def add_cell(bench_dir, name, chips=1, **changes):
    """A configuration ``name`` (deep96 at the tiny sizes, with
    ``changes``) and its batch cell ``<name>.batch``; returns the spec."""
    cfg = json.loads((bench_dir / "configs" / "deep96.json").read_text())
    cfg.update(name=name, **changes)
    (bench_dir / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    spec = spec_of(bench_dir)
    spec["workloads"].append({"name": f"{name}.batch", "config": name,
                              "traffic": "batch", "chips": chips,
                              "why": "x"})
    return spec


def tiered(device_pages):
    return {"device_pages": device_pages}


@pytest.fixture
def calls(monkeypatch):
    """The served entry's keyword arguments and params, call by call."""
    from repro.core import scheduler

    real, seen = scheduler.stream_search, []

    def recording(consts, geom, params, *a, **kw):
        seen.append((params, kw))
        return real(consts, geom, params, *a, **kw)

    monkeypatch.setattr(scheduler, "stream_search", recording)
    return seen


def test_tiered_store_serves_at_partial_residency(bench_dir, calls):
    spec = add_cell(bench_dir, "tier24", layout=tiered(SERVES))
    res = run.run_cell(spec, "tier24.batch", SEED, 1.0, False,
                       bench_dir=bench_dir, allow_cpu=True)
    assert res["correct"], res["checks"]
    c = res["_log"]["counters"]
    assert c["resident_fraction"] == SERVES / 32
    assert c["demand_fetches"] > 0 and c["page_misses"] > 0
    assert c["page_hits"] > 0 and c["stalls"] > 0
    assert {"prefetch_issued", "prefetch_hits"} <= set(c)
    stores = {id(kw["pagestore"]) for _, kw in calls}
    assert len(stores) == 1                 # one store, kept warm
    assert all(p.store_pages == 32 and "mesh" not in kw
               for p, kw in calls)


def test_tiered_store_livelock_reaches_the_caller(bench_dir):
    spec = add_cell(bench_dir, "tier16", layout=tiered(LIVELOCKS))
    with pytest.raises(RuntimeError, match="tiered page store livelock"):
        run.run_cell(spec, "tier16.batch", SEED, 1.0, False,
                     bench_dir=bench_dir, allow_cpu=True)


@pytest.mark.parametrize("changes, chips, names", [
    ({"layout": tiered(0)}, 1, "'layout.device_pages' is 0"),
    ({"layout": tiered(33)}, 1, "'layout.device_pages' is 33"),
    ({"layout": tiered(8.0)}, 1, "'layout.device_pages' is 8.0"),
    ({"layout": dict(tiered(8), prefetch=False)}, 1, "'layout' must be"),
    ({"layout": 8}, 1, "'layout' must be"),
    ({}, 4, "'shards' is 2"),
])
def test_bad_layout_fails_before_the_build(bench_dir, monkeypatch,
                                           changes, chips, names):
    def no_build(*a, **kw):
        raise AssertionError("the index build started")

    monkeypatch.setattr(index, "Build", no_build)
    spec = add_cell(bench_dir, "bad", chips=chips, **changes)
    with pytest.raises(ValueError, match="config bad: " + names):
        run.run_cell(spec, "bad.batch", SEED, 1.0, False,
                     bench_dir=bench_dir, allow_cpu=True)


def test_resident_config_calls_the_entry_as_before(bench_dir, calls):
    res = run.run_cell(spec_of(bench_dir), "deep96.batch", SEED, 1.0,
                       False, bench_dir=bench_dir, allow_cpu=True)
    assert res["correct"]
    assert "page_hits" not in res["_log"]["counters"]
    assert all(sorted(kw) == ["num_slots", "ring_capacity", "round_chunk"]
               and p.store_pages == 0 for p, kw in calls)


def test_layout_leaves_the_index_cache_key(tmp_path):
    # the key over fixed build sources: it guards the cached index, and
    # so setup_s, of every configuration that adds a layout
    for rel in index.BUILD_SOURCES:
        (tmp_path / rel).parent.mkdir(parents=True, exist_ok=True)
        (tmp_path / rel).write_text(f"# {rel}\n")
    cfg = json.loads((BENCH / "configs" / "deep96.json").read_text())
    key = index.cache_key(cfg, SEED, tmp_path)
    assert key == "3c7f65ca909d5d5f79bfd67e"
    assert index.cache_key(dict(cfg, layout=tiered(4)), SEED,
                           tmp_path) == key


FOUR_CHIPS = """
import json, pathlib, sys
import numpy as np
sys.path.insert(0, sys.argv[1])
import run
from repro.core import scheduler

real, ids, kws = scheduler.stream_search, [], []

def recording(*a, **kw):
    out = real(*a, **kw)
    ids.append(out[0])
    kws.append(sorted(kw))
    return out

scheduler.stream_search = recording
spec = json.loads(sys.argv[2])
out = {}
for cell in ("m4.batch", "m1.batch"):
    ids.clear()
    kws.clear()
    res = run.run_cell(spec, cell, int(sys.argv[3]), 1.0, False,
                       bench_dir=pathlib.Path(sys.argv[1]),
                       allow_cpu=True)
    res.pop("_log")
    out[cell] = {"res": res, "kw": kws[0], "ids": list(ids)}
a, b = out["m4.batch"]["ids"], out["m1.batch"]["ids"]
same = all(np.array_equal(x, y) for x, y in zip(a, b))
try:
    run.run_cell(spec, "t4.batch", int(sys.argv[3]), 1.0, False,
                 bench_dir=pathlib.Path(sys.argv[1]), allow_cpu=True)
    refused = None
except ValueError as e:
    refused = str(e)
print(json.dumps({c: {k: v for k, v in o.items() if k != "ids"}
                  for c, o in out.items()}
                 | {"ids_equal": same, "refused": refused}))
"""


def test_four_chip_cell_serves_over_its_mesh(bench_dir):
    """On four CPU devices: the four-chip cell serves through the mesh
    and reports four devices; the one-chip cell of the same
    configuration serves through the one-device stepper, with the same
    ids call by call. A tiered store on the mesh is the program's to
    refuse, and its refusal reaches the caller."""
    spec = add_cell(bench_dir, "m4", chips=4, shards=4)
    spec["workloads"].append(dict(spec["workloads"][-1], name="m1.batch",
                                  chips=1))
    tiered_spec = add_cell(bench_dir, "t4", chips=4, shards=4,
                           layout=tiered(16))
    spec["workloads"].append(tiered_spec["workloads"][-1])
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", FOUR_CHIPS, str(bench_dir),
         json.dumps(spec), str(SEED)],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    four, one = out["m4.batch"], out["m1.batch"]
    assert four["res"]["correct"], four["res"]["checks"]
    assert four["res"]["device"]["count"] == 4
    assert "mesh" in four["kw"]
    assert one["res"]["correct"] and one["res"]["device"]["count"] == 1
    assert "mesh" not in one["kw"]
    assert out["ids_equal"]
    assert "the tiered page store runs on the sim" in (
        out["refused"] or "")
