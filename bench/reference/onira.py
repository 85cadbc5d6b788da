"""Plain reference of the Onira in-order core (the paper's section 5.1
case study): one instruction issued per cycle, a register scoreboard,
non-blocking loads with at most four in flight, one store at a time
through the outgoing buffer, and a taken branch that stalls issue for
``flush_cycles``.  Each core talks to its own memory over a link of
``mem_latency`` cycles; the memory serves one request per cycle and
answers loads only.

Instructions are ``[op, rd, rs1, imm]``: 1 ADDI rd = rs1 + imm, 2 LOAD
rd = [rs1], 3 STORE [rs1] = rd, 4 BNEZ rs1 by imm, 5 HALT; op 0 is an
empty slot.
"""
from __future__ import annotations

from benchlib.refengine import Kind, OP, P0, P1, Num, RefSim, new_msg, \
    reply, wrap32

ADDI, LOAD, STORE, BNEZ, HALT = 1, 2, 3, 4, 5
MEM_READ, MEM_DATA, MEM_WRITE = 1, 2, 3     # message opcodes
N_REGS = 33


def _reg(i):
    return min(max(int(i), 0), N_REGS - 1)


def cpu_tick(s, v, t, prm, num):
    f = num.f
    msg, got = v.recv(0)
    if got:
        if 0 <= msg[P1] < N_REGS:
            s["busy"][msg[P1]] = 0
        s["pending"] -= 1
    halted = s["done"] > 0
    flushing = f(t + num.eps) < s["stall_until"]
    pc = min(max(s["pc"], 0), len(s["prog"]) - 1)
    op, rd, rs1, imm = s["prog"][pc]
    regs, busy = s["regs"], s["busy"]
    can_issue = not halted and not flushing
    src_busy = busy[_reg(rs1)] > 0
    dst_busy = busy[_reg(rd)] > 0
    src = regs[_reg(rs1)]
    do_alu = can_issue and op == ADDI and not src_busy
    if do_alu and 0 <= rd < N_REGS:
        regs[rd] = wrap32(src + imm)
    sent_l = v.send(0, new_msg(MEM_READ, p0=src, p1=rd),
                    when=(can_issue and op == LOAD and not src_busy
                          and s["pending"] < 4))
    if sent_l:
        if 0 <= rd < N_REGS:
            busy[rd] = 1
        s["pending"] += 1
    sent_s = v.send(0, new_msg(MEM_WRITE, p0=src, p1=32),
                    when=(can_issue and op == STORE and not src_busy
                          and not dst_busy))
    do_br = can_issue and op == BNEZ and not src_busy
    taken = do_br and regs[_reg(rs1)] != 0
    do_halt = can_issue and op == HALT
    if do_halt:
        s["done"] = 1
        s["halt_time"] = t
    issued = do_alu or sent_l or sent_s or do_br or do_halt
    if issued:
        s["pc"] = pc + imm if taken else pc + 1
        s["retired"] += 1
    if taken:
        s["stall_until"] = f(t + prm["flush_cycles"])
    s["stalls"] += can_issue and not issued
    nxt = s["stall_until"] if flushing and not halted else -1.0
    return got or issued or flushing, nxt


def mem_tick(s, v, t, prm, num):
    msg, got = v.recv(0, when=v.can_send(0))
    if got:
        if msg[OP] == MEM_READ:
            v.send(0, reply(msg, MEM_DATA, p0=msg[P0], p1=msg[P1]))
        s["served"] += 1
    return got, -1.0


def inputs(config: dict, rng) -> dict:
    """The programs, one per core, in the configuration's order (the
    same for every seed)."""
    return {"programs": [config["programs"][name]
                         for name in config["program_order"]]}


def simulate(config: dict, inputs: dict, point: dict, until: float,
             num=None, max_epochs: int = 2_000_000) -> dict:
    """Run one design point (all programs side by side) to ``until``.

    ``inputs["programs"]`` lists the programs; ``point`` may set
    ``conn_latency`` (every core-memory link) and
    ``kind.cpu.flush_cycles``.  Time is kept in the configuration's
    ``time_dtype`` unless ``num`` says otherwise."""
    progs = inputs["programs"]
    slots = config["program_slots"]
    cpus = []
    for prog in progs:
        prog = [list(map(int, ins)) for ins in prog]
        prog += [[0, 0, 0, 0]] * (slots - len(prog))
        cpus.append({"prog": prog, "pc": 0, "regs": [0] * N_REGS,
                     "busy": [0] * N_REGS, "pending": 0, "retired": 0,
                     "stalls": 0, "done": 0, "halt_time": 0.0,
                     "stall_until": 0.0})
    mems = [{"served": 0} for _ in progs]
    sim = RefSim([Kind("cpu", cpu_tick, cpus, 1, config["cpu_buffer"]),
                  Kind("mem", mem_tick, mems, 1, config["mem_buffer"])],
                 num or Num(config["time_dtype"]))
    lat = point.get("conn_latency", config["mem_latency"])
    for i in range(len(progs)):
        sim.connect([sim.port("cpu", i, 0), sim.port("mem", i, 0)], lat)
    flush = sim.num.f(point.get("kind.cpu.flush_cycles",
                                config["flush_cycles"]))
    out = sim.run(until, max_epochs, {"cpu": {"flush_cycles": flush}})
    out.update(retired=[c["retired"] for c in cpus],
               halt_time=[c["halt_time"] for c in cpus],
               done=[c["done"] for c in cpus])
    return out
