"""Plain reference of the memsys model: cores, private L1s and one DRAM
port behind a shared crossbar (the Smart Ticking evaluation system).

Each core issues ``reads`` reads, one outstanding at a time, with a
think time between them; its addresses either stride by one 64-byte line
(``seq``) or follow a 31-bit linear congruential stream.  Each L1 is
direct-mapped with ``l1_sets`` sets and one MSHR, and may force a hit
with probability ``extra_hit_rate`` by hashing the address.  The DRAM
port serves one request per cycle.  Cores and L1s meet over one-cycle
links; every L1's memory side and the DRAM port share one round-robin
crossbar of ``xbar_latency`` cycles.
"""
from __future__ import annotations

from benchlib.refengine import (Kind, P0, P1, OP, Num, RefSim, new_msg,
                                reply, wrap32)

READ_REQ, READ_RESP = 1, 2
LCG_A, LCG_C, MASK31 = 1103515245, 12345, 0x7FFFFFFF
TWO31 = 2147483648.0


def _lcg(x: int) -> int:
    return (x * LCG_A + LCG_C) & MASK31


def core_tick(s, v, t, prm, num):
    f = num.f
    _, got = v.recv(0)
    s["outstanding"] -= got
    computing = f(t + num.eps) < s["next_issue"]
    can_issue = s["remaining"] > 0 and s["outstanding"] < 1 \
        and not computing
    addr = wrap32(s["addr"] + 64) if s["seq"] > 0 else _lcg(s["addr"])
    sent = v.send(0, new_msg(READ_REQ, p0=addr, p1=s["tag"]),
                  when=can_issue)
    if sent:
        s["addr"] = addr
        s["remaining"] -= 1
        s["outstanding"] += 1
        s["next_issue"] = f(t + f(s["think"] * prm["think_scale"]))
    nxt = s["next_issue"] if (computing and s["remaining"] > 0
                              and s["outstanding"] < 1) else -1.0
    return got or sent, nxt


def l1_tick(s, v, t, prm, num):
    n_sets = len(s["tags"])
    fill, rgot = v.recv(1, when=v.can_send(0))
    if rgot:
        a = fill[P0]
        s["tags"][(a // 64) % n_sets] = a // 64
        v.send(0, new_msg(READ_RESP, p0=a, p1=fill[P1]))
        s["mshr_busy"] = 0
    can_hit = v.can_send(0)
    can_miss = s["mshr_busy"] == 0 and v.can_send(1)
    msg, got = v.peek(0)
    accept = False
    if got:
        a = msg[P0]
        forced = num.f(_lcg(a)) < num.f(prm["extra_hit_rate"] * TWO31)
        hit = s["tags"][(a // 64) % n_sets] == a // 64 or forced
        accept = can_hit if hit else can_miss
        if accept:
            v.recv(0)
            if hit:
                v.send(0, reply(msg, READ_RESP, p0=a, p1=msg[P1]))
                s["hits"] += 1
            elif v.send(1, new_msg(READ_REQ, p0=a, p1=msg[P1])):
                s["mshr_busy"] = 1
                s["misses"] += 1
    return rgot or accept, -1.0


def dram_tick(s, v, t, prm, num):
    msg, got = v.recv(0, when=v.can_send(0))
    if got:
        if msg[OP] == READ_REQ:
            v.send(0, reply(msg, READ_RESP, p0=msg[P0], p1=msg[P1]))
        s["served"] += 1
    return got, -1.0


def inputs(config: dict, rng) -> dict:
    """One job's per-core inputs by the ``mixed`` rule: think times
    uniform on ``[0, think_max]``, each core streaming sequentially or
    not with even odds, and a start address below ``2**addr_bits``."""
    if config["pattern"] != "mixed":
        raise ValueError(f"no generator for pattern {config['pattern']!r}")
    n = config["cores"]
    return {"think": rng.integers(0, config["think_max"] + 1, n).tolist(),
            "seq": rng.integers(0, 2, n).tolist(),
            "addr": rng.integers(0, 1 << config["addr_bits"], n).tolist()}


def simulate(config: dict, inputs: dict, point: dict, until: float,
             num=None, max_epochs: int = 2_000_000) -> dict:
    """Run one design point to ``until`` and return its statistics.

    ``inputs`` holds the per-core ``think``, ``seq`` and ``addr`` lists;
    ``point`` may set ``conn_latency[-1]`` (the crossbar) and
    ``kind.l1.extra_hit_rate`` in place of the configuration's.  Time
    is kept in the configuration's ``time_dtype`` unless ``num`` says
    otherwise."""
    n = config["cores"]
    cores = [{"remaining": config["reads_per_core"], "outstanding": 0,
              "addr": int(inputs["addr"][i]), "seq": int(inputs["seq"][i]),
              "think": float(inputs["think"][i]), "tag": i,
              "next_issue": 0.0} for i in range(n)]
    l1s = [{"tags": [-1] * config["l1_sets"], "mshr_busy": 0, "hits": 0,
            "misses": 0} for _ in range(n)]
    dram = [{"served": 0}]
    sim = RefSim([Kind("core", core_tick, cores, 1, config["core_buffer"]),
                  Kind("l1", l1_tick, l1s, 2, config["l1_buffer"]),
                  Kind("dram", dram_tick, dram, 1, config["dram_buffer"])],
                 num or Num(config["time_dtype"]))
    for i in range(n):
        sim.connect([sim.port("core", i, 0), sim.port("l1", i, 0)],
                    config["link_latency"])
    xbar = [sim.port("l1", i, 1) for i in range(n)] \
        + [sim.port("dram", 0, 0)]
    sim.connect(xbar, point.get("conn_latency[-1]", config["xbar_latency"]))
    for i in range(n):
        sim.ports[sim.port("l1", i, 1)].peer = sim.port("dram", 0, 0)
    f = sim.num.f
    params = {"core": {"think_scale": f(point.get("kind.core.think_scale",
                                                  1.0))},
              "l1": {"extra_hit_rate": f(point.get(
                  "kind.l1.extra_hit_rate", config["extra_hit_rate"]))},
              "dram": {}}
    out = sim.run(until, max_epochs, params)
    out.update(
        reads_done=sum(d["served"] for d in dram),
        hits=sum(c["hits"] for c in l1s),
        misses=sum(c["misses"] for c in l1s),
        remaining=sum(c["remaining"] for c in cores),
        outstanding=sum(c["outstanding"] for c in cores))
    return out
