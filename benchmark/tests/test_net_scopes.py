"""The network stack's layers from a profiler trace: self time of the ops
under each `net.*` named scope (net_scopes.py), and nothing to read where
the program carries no such scope."""

from __future__ import annotations

from benchmark import net_scopes, spec as spec_mod

MS = 10**6
ROOT = "jit(run_to)/while/body/cond/branch_0_fun/win.handlers/while/body"


def test_self_times_by_hand():
    names = {"fusion.1": f"{ROOT}/net.nic/min",
             "fusion.2": f"{ROOT}/net.router/net.nic/add",
             "fusion.3": f"{ROOT}/net.router/select",
             "fusion.4": f"{ROOT}/net.udp/argmax",
             "fusion.5": f"{ROOT}/add"}
    ops = [("while.9", 0, 20 * MS),
           ("fusion.1", 1 * MS, 2 * MS),
           ("fusion.2", 2 * MS, 4 * MS),    # innermost scope wins
           ("fusion.3", 4 * MS, 7 * MS),
           ("copy.6", 7 * MS, 8 * MS),      # no op_name: the router's
           ("fusion.4", 8 * MS, 9 * MS),
           ("fusion.5", 9 * MS, 12 * MS)]   # in no net.* scope
    per = net_scopes.self_times(ops, names)
    assert per == {"net.nic": 3 * MS, "net.router": 4 * MS,
                   "net.udp": 1 * MS,
                   None: 3 * MS + 9 * MS}  # fusion.5 and the loop's gaps
    assert sum(per.values()) == 20 * MS


def test_layer_of_an_op_name():
    assert net_scopes.layer(f"{ROOT}/net.router/net.udp/x") == "net.udp"
    assert net_scopes.layer(f"{ROOT}/add") is None
    assert net_scopes.layer(None) is None


def test_readers_are_entries_of_the_flood_cell():
    sp = spec_mod.Spec.load()
    entries = {m["name"]: m for m in sp.doc["per_layer"]}
    for name in ("net.router_ms_per_window", "net.nic_ms_per_window"):
        assert entries[name]["workloads"] == ["udp_flood_10k.dense"]
        assert callable(sp.reader(name))
        # no trace, nothing to read
        assert sp.reader(name)(type("R", (), {"trace": None})) is None
