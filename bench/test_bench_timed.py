"""The served cells admit on the benchmark's timed planner; the gateway
then plans exactly what it plans on its own clone of the fleet planner."""
import dataclasses

from bench import harness, rehearsal, system


def _served(cell):
    r = harness.resolve(cell)
    r["mix"].update(rehearsal.TINY[cell]["mix"])
    drv = harness.plugin("loops", "served").Loop(r["config"], r["mix"],
                                                 rehearsal.SEED)
    drv.setup()
    return drv


def test_timed_planner_plans_bit_identically():
    from repro.core.controlplane import ShardedFleet, StreamingGateway
    from repro.core.scheduler.planner import CarbonPlanner

    timed = _served("metro_fanout_200.served")
    timed.window(1e6)                  # the whole tiny stream
    cfg, fl = timed.config, timed.config["fleet"]
    fleet = ShardedFleet(system.ftns(cfg), n_shards=fl["n_shards"],
                         parallel=fl["parallel"],
                         batch_backend=cfg["admission_tier"],
                         shard_backend=fl["shard_backend"])
    rep = StreamingGateway(fleet, **timed.mix["gateway"]).run(
        iter(timed.tjobs))
    a, b = timed.rep, rep
    assert a.total_planned_g == b.total_planned_g
    assert a.total_actual_g == b.total_actual_g
    assert a.ledger_total_g == b.ledger_total_g
    assert a.outcomes == b.outcomes
    assert timed.planner.sweeps and any(s.device
                                        for s in timed.planner.sweeps)
    plain = CarbonPlanner(system.ftns(cfg), field=fleet.field,
                          batch_backend=cfg["admission_tier"])
    for s in timed.planner.sweeps:
        again = plain.plan_batch(s.jobs)
        assert all(system.same_cell(a, b) for a, b in zip(again, s.plans))
        assert [dataclasses.astuple(p) for p in again] == \
            [dataclasses.astuple(p) for p in s.plans]
