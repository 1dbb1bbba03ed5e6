"""Device time of a round by the program's spans.

The program names the layers of its sharded step with ``jax.named_scope``
(``laq.fwd_bwd``, ``laq.lazy_rule``, ``laq.wire.quantize``,
``laq.wire.codec``, ``laq.aggregate``, ``laq.optimizer``; see
``src/repro/core/spans.py``).  A scope lands in the ``op_name`` metadata of
the optimized HLO; the profiler's ``XLA Ops`` events name only the HLO
instruction (``%fusion.769 = ...``), with no metadata.  So the reduction
maps each event to its instruction, and the instruction to the innermost
``laq.*`` name of its ``op_name``.  An instruction whose own metadata is
empty takes, for a fusion, its fused computation's root's (walking back
from the root to the first instruction that has an ``op_name``), else that
of the instruction that calls its computation (the loop whose body it is).
An instruction that the compiler made with no metadata anywhere (a convert
it put before a dot, the pieces it split a long reduce-window into) takes
the span of the first of its users that has one, else of its operands; so
does one that computes from constants alone (an iota mask, a zero fill,
which the compiler hoists out of the span that uses it), and one named
after an argument of the step (the compiler's copies of a parameter into
the layout its first user wants).
Instruction names are unique in the module, so operations inside loop
bodies are mapped too.

Each event counts its own time (``traces.self_ns``: a loop's event less the
body's events), so the spans and the unscoped rest partition the device's
busy time.  Times are summed over the traced window, averaged over the
chips used and divided by the rounds.

The HLO is that of the step the run compiled: ``ctx["hlo"]`` where the
harness supplies it, else the step is lowered again from the cell and
compiled (the persistent compilation cache returns the executable the run
used), once per run.  Where the HLO has no ``laq.*`` scope at all, as for a
program without spans, every reader here returns ``None``.
"""
from __future__ import annotations

import re

import traces

SCOPE = re.compile(r"laq\.[A-Za-z0-9_]+(?:\.[A-Za-z0-9_]+)*")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_EVENT = re.compile(r"^%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_CALLED = re.compile(r"(?:body|condition|calls|to_apply)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_REF = re.compile(r"%([\w.\-]+)")


def innermost(op_name: str | None):
    """The innermost ``laq.*`` name of an ``op_name``, or ``None``."""
    if not op_name:
        return None
    hits = SCOPE.findall(op_name)
    return hits[-1] if hits else None


def parse(hlo: str):
    """``(instructions, computations, callers, operands)`` of an HLO
    module's text: each instruction's name -> ``(op_name or None, opcode,
    fused computation or None)``; each computation's name -> its
    instruction names in order, the root last; each called computation ->
    the first instruction that calls it; each instruction -> the names it
    refers to (its operands among them)."""
    instrs, comps, callers, refs, cur, root = {}, {}, {}, {}, None, None
    for line in hlo.splitlines():
        s = line.strip()
        if s.endswith("{") and " = " not in s.split("(")[0]:
            head = s.split()[1] if s.startswith("ENTRY") else s.split()[0]
            cur, root = head.lstrip("%"), None
            comps[cur] = []
            continue
        if s == "}":
            if cur is not None and root is not None:
                comps[cur].remove(root)
                comps[cur].append(root)
            cur = None
            continue
        m = _INSTR.match(line)
        if m is None or cur is None:
            continue
        name, rest = m.group(1), m.group(2)
        op = _OP_NAME.search(rest)
        code = _OPCODE.search(" " + rest)
        calls = _CALLS.search(rest) if code and code.group(1) == "fusion" else None
        instrs[name] = (op.group(1) if op else None,
                        code.group(1) if code else None,
                        calls.group(1) if calls else None)
        comps[cur].append(name)
        refs[name] = _REF.findall(rest.split(", metadata=")[0])
        if s.startswith("ROOT"):
            root = name
        called = _CALLED.findall(rest)
        for b in _BRANCHES.findall(rest):
            called += [c.strip().lstrip("%") for c in b.split(",")]
        for c in called:
            callers.setdefault(c, name)
    return instrs, comps, callers, refs


def scope_map(hlo: str) -> dict:
    """Each instruction's name -> its innermost ``laq.*`` scope or
    ``None``."""
    instrs, comps, callers, refs = parse(hlo)
    home = {n: c for c, names in comps.items() for n in names}
    operands = {n: [r for r in rs if home.get(r) == home[n]]
                for n, rs in refs.items()}
    users = {}
    for n, ops in operands.items():
        for o in ops:
            users.setdefault(o, []).append(n)
    memo = {}

    def op_name_of(name, depth=0):
        if name in memo:
            return memo[name]
        op, _, calls = instrs[name]
        if op is None and depth < 16:
            for inner in reversed(comps.get(calls, ())):
                op = op_name_of(inner, depth + 1)
                if op is not None:
                    break
        if op is None and depth < 16 and callers.get(home[name]) in instrs:
            op = op_name_of(callers[home[name]], depth + 1)
        memo[name] = op
        return op

    # instructions come after their operands in the text
    constant = {}
    for names in comps.values():
        for n in names:
            code = instrs[n][1]
            constant[n] = code in ("constant", "iota") or (
                code not in ("parameter", None)
                and all(constant.get(o, False) for o in operands[n]))

    scopes = {n: innermost(op_name_of(n)) for n in instrs}
    arguments = {op for op, code, _ in instrs.values()
                 if code == "parameter" and op is not None}
    # what the compiler made with no metadata, from constants alone, or
    # from an argument under its name: the span of its users, else of its
    # operands, through chains of such instructions
    bare = [n for n in instrs
            if op_name_of(n) is None or (scopes[n] is None and (
                constant[n] or op_name_of(n) in arguments))]
    for neighbours, order in ((users, bare[::-1]), (operands, bare)):
        changed = True
        while changed:
            changed = False
            for n in order:
                if scopes[n] is None:
                    hit = next((scopes[m] for m in neighbours.get(n, ())
                                if scopes[m] is not None), None)
                    if hit is not None:
                        scopes[n], changed = hit, True
    return scopes


def instruction_of(event_name: str) -> str:
    """The HLO instruction an ``XLA Ops`` event names."""
    m = _EVENT.match(event_name)
    return m.group(1) if m else event_name


def milliseconds(trace, span, planes, rounds: int, scopes: dict) -> dict:
    """Device milliseconds per round of each scope (``None`` for the
    operations with no ``laq.*`` scope or missing from the HLO), the own
    time of each event in the window, averaged over ``planes``."""
    t0, t1 = span
    total = {}
    for p in planes:
        ops = traces.clip(trace.devices[p], t0, t1)
        for own, name in zip(traces.self_ns(ops), ops.name):
            s = scopes.get(instruction_of(name))
            total[s] = total.get(s, 0.0) + float(own)
    return {s: v * 1e-6 / len(planes) / rounds for s, v in total.items()}


def compile_hlo(ctx) -> str:
    """The optimized HLO of the cell's step, lowered as the harness lowers
    it (``jax.jit(step, donate_argnums=0)`` on the state and one batch, in
    their shardings) from shapes alone."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import harness
    import traffic
    prog = harness._program()
    mix, W = ctx["mix"], ctx["chips"]
    built = harness.build(prog, ctx["config"], mix, W)
    state = prog["train_state_specs"](built["cfg"], built["mesh"],
                                      built["strategy"], built["opt"],
                                      ("data",))
    batch_sharding = NamedSharding(built["mesh"], P("data", None))
    batch = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                       sharding=batch_sharding),
        jax.eval_shape(lambda k: traffic.split(traffic.round_streams(
            k, range(1), mix, ctx["config"]["vocab"], W)[0]),
            traffic.seed_key(0)))
    return jax.jit(built["step"], donate_argnums=0).lower(
        state, batch).compile().as_text()


def per_round_ms(ctx):
    """``{scope: ms per round}`` of the traced run, or ``None`` without a
    trace or where the step's HLO has no ``laq.*`` scope.  Computed once
    per run and kept in ``ctx``."""
    if ctx.get("trace") is None:
        return None
    if "scope_ms" not in ctx:
        if ctx.get("hlo") is None:
            ctx["hlo"] = compile_hlo(ctx)
        scopes = scope_map(ctx["hlo"])
        ctx["scope_ms"] = (
            milliseconds(ctx["trace"], ctx["span"], ctx["planes"],
                         ctx["rounds"], scopes)
            if any(s is not None for s in scopes.values()) else None)
    return ctx["scope_ms"]


def reader(scope: str | None):
    """A metric's ``read(ctx)``: the device ms per round of ``scope``
    (``None``: the unscoped operations)."""
    def read(ctx):
        ms = per_round_ms(ctx)
        if ms is None:
            return None
        return ms.get(scope, 0.0)
    return read
