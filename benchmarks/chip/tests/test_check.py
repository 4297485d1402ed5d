"""The comparison that decides ``correct``, driven through whole runs on
the CPU at a tiny size, with the harness's look for a chip skipped: a sound
run passes, the control (the reference in three bf16 passes, standing in
the program's place) fails, and so does each fault the timed path can
have: an answer altered where it is produced, half of a batch left out,
and on a sharded corpus the exchange between chips left out."""
import json
import os
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

import run

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_cell(config: str, traffic: str, shards: int = 1) -> SimpleNamespace:
    """The cell at a size the CPU runs in seconds; ``shards`` > 1 splits
    the corpus over that many virtual devices, as a four-chip cell would."""
    with open(os.path.join(CHIP, "configs", config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(CHIP, "traffic", traffic + ".json")) as f:
        tr = json.load(f)
    cfg["shards"] = shards
    cfg["rows"] = 2048 * shards
    if shards == 1:
        cfg["build"]["backend"] = "kernel"      # interpret mode on the CPU
    if tr["loop"] == "closed":
        tr.update(batch=64, pool=128, check_sample=2)
    else:
        # microbatches of 4 fill up at this rate, so each holds real
        # queries past its first half
        tr.update(rate_per_s=200, max_batch=4, pool=128, check_sample=64)
    return SimpleNamespace(name=f"{config}.{traffic}", chips=shards,
                           config=cfg, traffic=tr, generate=run.generate,
                           drive=run.LOOPS[tr["loop"]], end_to_end=[],
                           per_layer=[])


def run_tiny(cell, *, control=False):
    return run.run_cell(cell, 2**31 + 3, 1.0, False,
                        jax.devices()[:cell.chips], t0=time.perf_counter(),
                        control=control)


def correct(cell, numbers) -> bool:
    return run.verdict(numbers, cell.config["limits"])[0]


@pytest.mark.parametrize("config,traffic,shards", [
    ("msmarco-768-chip", "batch-k100", 1),
    ("msmarco-768-chip", "served-k10", 1),
    ("deep96-chip", "batch-k10", 1),
    ("msmarco-768-chip", "batch-k10", 4),
])
def test_sound_answers_match_and_the_control_fails(config, traffic, shards):
    cell = tiny_cell(config, traffic, shards)
    res = run_tiny(cell, control=True)
    got, ctl = res["numbers"], res["control"]
    assert correct(cell, got), got
    assert not correct(cell, {**ctl, "failed": 0}), ctl


def _altered(search):
    def wrapped(self, queries, k, **kw):
        sims, ids, st = search(self, queries, k, **kw)
        return sims, ids.at[0, 0].set(ids[0, 0] ^ 1), st
    return wrapped


def _half_left_out(search):
    def wrapped(self, queries, k, **kw):
        sims, ids, st = search(self, queries, k, **kw)
        h = sims.shape[0] // 2
        return (jnp.concatenate([sims[:h], sims[:h]]),
                jnp.concatenate([ids[:h], ids[:h]]), st)
    return wrapped


@pytest.mark.parametrize("config,traffic,fault", [
    ("msmarco-768-chip", "batch-k100", _altered),
    ("msmarco-768-chip", "served-k10", _altered),
    ("msmarco-768-chip", "batch-k100", _half_left_out),
    ("msmarco-768-chip", "served-k10", _half_left_out),
])
def test_a_broken_search_is_not_correct(config, traffic, fault, monkeypatch):
    from repro.search import SearchEngine
    monkeypatch.setattr(SearchEngine, "search", fault(SearchEngine.search))
    cell = tiny_cell(config, traffic)
    assert not correct(cell, run_tiny(cell)["numbers"])


def test_a_merge_without_the_exchange_between_chips_is_not_correct(
        monkeypatch):
    import repro.dist.collectives as coll

    # each chip keeps its own shard's top-k: what a merge that skipped the
    # all-gather would return
    monkeypatch.setattr(coll, "topk_allgather_merge",
                        lambda sims, gids, k, axis_names: (sims[:, :k],
                                                           gids[:, :k]))
    cell = tiny_cell("msmarco-768-chip", "batch-k10", shards=4)
    numbers = run_tiny(cell)["numbers"]
    assert numbers["id_mismatch"] > 0
    assert not correct(cell, numbers)


@pytest.mark.parametrize("cell,loop", [
    ("msmarco-768-chip.batch-k100", "closed"),
    ("deep96-chip.batch-k10", "closed"),
    ("msmarco-768-chip.served-k10", "open"),
])
def test_a_cell_with_no_code_of_its_own_runs_the_general_loop(cell, loop):
    got = run.load_cell(cell)
    assert got.drive is run.LOOPS[loop]
    assert got.generate is run.generate


STUB_CONFIG = """
import jax
import jax.numpy as jnp

CALLS = []


def generate(cfg, seed, devices):
    CALLS.append(seed)
    key_db, key_q = jax.random.split(jax.random.PRNGKey(seed & 0xFFFF))
    db = jax.random.normal(key_db, (cfg["rows"], cfg["dim"]), jnp.float32)
    return jax.device_put(db, devices[0]), key_q, None
"""

STUB_MIX = """
import run

CALLS = []


def drive(ctx):
    CALLS.append(ctx.seed)
    return run.closed_loop(ctx)
"""


def test_a_mix_and_a_configuration_with_code_of_their_own_drive_the_run(
        tmp_path):
    """A later cell whose configuration and mix need code adds
    ``configs/<name>.py`` and ``traffic/<name>.py`` beside their files;
    the harness finds both by name and runs them."""
    chip = tmp_path / "bench"
    (chip / "configs").mkdir(parents=True)
    (chip / "traffic").mkdir()
    cell = tiny_cell("msmarco-768-chip", "batch-k100")
    (chip / "configs" / "uniform.json").write_text(json.dumps(cell.config))
    (chip / "configs" / "uniform.py").write_text(STUB_CONFIG)
    (chip / "traffic" / "stub.json").write_text(json.dumps(cell.traffic))
    (chip / "traffic" / "stub.py").write_text(STUB_MIX)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "uniform", "file": "bench/configs/uniform.json"}],
        "workloads": [{"name": "uniform.stub", "config": "uniform",
                       "traffic": "stub", "chips": 1}],
        "end_to_end": [], "per_layer": []}))
    got = run.load_cell("uniform.stub", root=str(tmp_path), chip=str(chip))
    assert got.drive is not run.closed_loop
    assert got.generate is not run.generate
    res = run_tiny(got)
    assert got.drive.__globals__["CALLS"] == [2**31 + 3]
    # once for the run, once more for the check
    assert got.generate.__globals__["CALLS"] == [2**31 + 3] * 2
    assert correct(got, res["numbers"]), res["numbers"]
    assert res["out"].values["queries_per_s"] > 0
