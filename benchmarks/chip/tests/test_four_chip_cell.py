"""The four-chip cell over the whole MS MARCO corpus, driven on the CPU at
a tiny size over four virtual devices: the harness finds the
configuration's own ``generate``, which pads a corpus that does not split
evenly over the chips; the sound run is correct and the control is not."""
import time

import jax
import numpy as np

import run

CELL = "msmarco-768-4chip.batch-k10"


def tiny(cell, rows: int):
    """``cell`` at ``rows`` rows, with the traffic cut to what the CPU runs
    in seconds."""
    cell.config.update(rows=rows)
    cell.config["build"]["global_rows"] = rows
    cell.traffic.update(batch=64, pool=128, check_sample=2)
    return cell


def test_the_cell_finds_its_own_generate():
    cell = run.load_cell(CELL)
    assert cell.generate is not run.generate
    assert cell.generate.__module__.startswith("bench_configs")
    assert cell.drive is run.LOOPS["closed"]
    assert cell.chips == cell.config["shards"] == 4
    assert cell.config["rows"] == cell.config["build"]["global_rows"]
    assert cell.config["rows"] % cell.chips, "the pad is what it exercises"
    names = {m["name"] for m in cell.per_layer}
    assert {"sharded.collective_share.batch",
            "sharded.busy_spread.batch"} <= names


def test_the_pad_rows_are_zero_and_split_evenly():
    cell = tiny(run.load_cell(CELL), 2047)
    db, _, mesh = cell.generate(cell.config, 2**31 + 5, jax.devices()[:4])
    assert mesh.devices.size == 4 and db.shape == (2048, 768)
    assert {s.data.shape[0] for s in db.addressable_shards} == {512}
    rows = np.asarray(db)
    assert not rows[2047:].any()
    assert (np.linalg.norm(rows[:2047], axis=1) > 0).all()


def test_sound_answers_match_and_the_control_fails():
    cell = tiny(run.load_cell(CELL), 2047)
    res = run.run_cell(cell, 2**31 + 3, 1.0, False, jax.devices()[:4],
                       t0=time.perf_counter(), control=True)
    # a near query drawn from the pad row would be NaN: not on this seed
    assert all(np.isfinite(q).all() for q, _, _ in res["out"].answers)
    got, ctl = res["numbers"], res["control"]
    assert run.verdict(got, cell.config["limits"])[0], got
    assert not run.verdict({**ctl, "failed": 0}, cell.config["limits"])[0], ctl
