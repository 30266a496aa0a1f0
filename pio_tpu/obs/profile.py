"""From a device profile of `pio train` to seconds by name.

    python -m pio_tpu.obs.profile <profile dir or .xplane.pb> [--ops SCOPE]

Two sets of names meet in a profile (docs/observability.md "Training"):

  * the program's SPANS (`train`, `train.setup`, `als.partition`,
    `persist.pickle`, ...): a `Tracer(device=True)` opens each one as a
    `jax.profiler.TraceAnnotation` too, so they lie on the host plane's
    main-thread line, on the clock of the device's operations;
  * the SCOPES of the device program (`als.user/als.gather`, ...):
    `jax.named_scope`s, which reach the compiled module's text
    (`metadata={op_name=".../als.user/als.gather/..."}` per instruction)
    and not the trace, whose events carry the HLO instruction's name
    only.

The compiled text needs no second compile and no file beside the
profile: the profiler stores every module it saw run as an HLO proto in
the `/host:metadata` plane, which jaxlib's own `XlaComputation` prints
with metadata. jax's `ProfileData` reader does not expose that plane's
event metadata, so `hlo_texts` walks the file's protobuf wire format
itself (three message types, thirty lines).

`reduce` then gives, per job (one root span) and per chip:

  * device seconds per scope: every event of a chip's `XLA Ops` line,
    less what its nested events cover (a `while` does not count its body
    twice), joined to a scope by its instruction's name within the
    `XLA Modules` event that holds it. An instruction with no scope of
    its own takes that of what it calls or of its neighbours
    (`module_scopes` says how, and `by_rule` how much came which way).
    What still has none is `unscoped`, never dropped, so scopes +
    unscoped = own time of all events = busy time;
  * device idle seconds per span: the gaps between the chip's
    operations, each split by OVERLAP with the innermost span open on
    the main thread (a gap that runs from one job's persist into the
    next job's partition is divided between them); what lies outside
    every span is `outside`, so spans + outside = idle time.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import os
import re
import sys
from collections import Counter, defaultdict

ROOT = "train"
# the scope families of the device programs: the ALS trainer's and the
# sequence engine's block stack (models/seq_blocks.py)
SCOPE_PREFIX = ("als.", "seq.")
UNSCOPED = "unscoped"
OUTSIDE = "outside"
# a span's name: lower-case words with a dot between (or the root's).
# jax's own events on the same line (`PjitFunction(run)`, `shard_args`,
# `$train.py:140 run_train`) do not match
_SPAN_NAME = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")


# ---------------------------------------------------------------------------
# reading
# ---------------------------------------------------------------------------

def find_xplane(path: str) -> str:
    """The newest profile under a directory (or the file itself): an
    `.xplane.pb` as the jax profiler writes it, or one gzipped."""
    if os.path.isfile(path):
        return path
    found = sorted(
        glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
        + glob.glob(os.path.join(path, "**", "*.xplane.pb.gz"),
                    recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    val = shift = 0
    while True:
        c = buf[i]
        i += 1
        val |= (c & 0x7F) << shift
        shift += 7
        if not c & 0x80:
            return val, i


def _fields(buf: bytes):
    """(field number, wire type, value) of one protobuf message; values
    of length-delimited fields are bytes, varints ints."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire in (1, 2, 5):
            size = 8 if wire == 1 else 4
            if wire == 2:
                size, i = _varint(buf, i)
            val = buf[i:i + size]
            i += size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, wire, val


def hlo_texts(space: bytes) -> dict[str, str]:
    """{module name as the trace has it: compiled HLO text with
    metadata} from a serialized profile's `/host:metadata` plane: XSpace.planes
    (1) -> XPlane.name (2), .event_metadata (4, a map entry whose value
    (2) is an XEventMetadata: .name (2), .stats (5)) -> XStat.bytes_value
    (6) = HloProto, whose field 1 is the HloModuleProto."""
    from jax._src.lib import xla_client

    options = xla_client._xla.HloPrintOptions.short_parsable()
    options.print_metadata = True
    options.print_percent = True
    options.print_backend_config = False
    options.print_large_constants = False
    out: dict[str, str] = {}
    for field, _, plane in _fields(space):
        if field != 1:
            continue
        entries, name = [], None
        for pf, _, pv in _fields(plane):
            if pf == 2:
                name = pv.decode()
            elif pf == 4:
                entries.append(pv)
        if name != "/host:metadata":
            continue
        for entry in entries:
            for ef, _, meta in _fields(entry):
                if ef != 2:
                    continue
                mod_name, proto = None, None
                for mf, _, mv in _fields(meta):
                    if mf == 2:
                        mod_name = mv.decode()
                    elif mf == 5:
                        for sf, sw, sv in _fields(mv):
                            if sf == 6 and sw == 2:
                                proto = sv
                if mod_name is None or proto is None:
                    continue
                module = next((v for f_, w_, v in _fields(proto)
                               if f_ == 1 and w_ == 2), None)
                if module is not None:
                    out[mod_name] = xla_client.XlaComputation(
                        module).get_hlo_module().to_string(options)
    return out


def read_profile(path: str) -> dict:
    """-> {"devices": {plane: {"ops": [(hlo line, start_ns, dur_ns)],
    "modules": [(name, start_ns, dur_ns)]}}, "spans": [(name, start_ns,
    dur_ns)] (the main thread's, by `_SPAN_NAME`), "hlo": {module:
    text}} from a profile directory or one .xplane.pb."""
    from jax.profiler import ProfileData

    path = find_xplane(path)
    with open(path, "rb") as f:
        space = f.read()
    if path.endswith(".gz"):
        space = gzip.decompress(space)
    devices: dict[str, dict] = {}
    spans: list = []
    for plane in ProfileData.from_serialized_xspace(space).planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            devices[plane.name] = {
                key: [(e.name, e.start_ns, e.duration_ns)
                      for e in lines[name].events] if name in lines else []
                for key, name in (("ops", "XLA Ops"),
                                  ("modules", "XLA Modules"))}
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                events = [(e.name, e.start_ns, e.duration_ns)
                          for e in line.events]
                if any(n == ROOT for n, _, _ in events):
                    spans += [e for e in events
                              if e[0] == ROOT or _SPAN_NAME.match(e[0])]
    return {"devices": devices, "spans": spans, "hlo": hlo_texts(space)}


# ---------------------------------------------------------------------------
# scopes of a compiled module
# ---------------------------------------------------------------------------

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s(.*)$")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"(?:calls|body|to_apply|condition)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_OPERAND = re.compile(r"%([\w.\-]+)")
# how an instruction came by its scope, most direct first
RULES = ("own", "called", "neighbour")


def _scope_pattern(prefix: str | tuple[str, ...]) -> re.Pattern:
    families = (prefix,) if isinstance(prefix, str) else tuple(prefix)
    return re.compile(r"(?<![\w.])(?:" + "|".join(
        re.escape(f) for f in families) + r")[\w.]*")


def scope_of_op_name(op_name: str,
                     prefix: str | tuple[str, ...] = SCOPE_PREFIX
                     ) -> str | None:
    """`jit(_train_jit)/while/body/als.user/als.gather/dot_general` ->
    `als.user/als.gather`: the path's components that are scopes, nested
    as the program nested them (`seq.mtp/seq.attn.full`: the prediction
    module's attention). A differentiated program wraps them
    (`transpose(jvp(seq.head_loss))`) and an inlined helper repeats the
    path it was called from: a scope counts wherever it stands, and a
    scope that comes again takes the path back to where it first
    stood."""
    parts: list[str] = []
    for found in _scope_pattern(prefix).findall(op_name):
        if found in parts:
            del parts[parts.index(found) + 1:]
        else:
            parts.append(found)
    return "/".join(parts) or None


def module_scopes(hlo_text: str,
                  prefix: str | tuple[str, ...] = SCOPE_PREFIX
                  ) -> dict[str, tuple[str, str]]:
    """{instruction name: (scope, rule)} for a compiled module (text
    printed with `%` before names). Rules, in order:

    `own`: the instruction's `op_name` holds the scope.
    `called`: it has none and calls computations (a fusion, a `while`):
      the scope most of the instructions called have.
    `neighbour`: the compiler made it (a copy, the pieces of a sort it
      split) or its lowering lost the path (`cumsum`'s reduce-window
      says `op_name="reduce_window_sum"` and no more): the scope of the
      instructions that use it, else of those it uses, within its
      computation, passed on until nothing changes. A guess, counted
      apart by `reduce`."""
    own: dict[str, str | None] = {}
    callees: dict[str, list[str]] = {}
    operands: dict[str, list[str]] = {}
    members: dict[str, list[str]] = defaultdict(list)
    computation = None
    for line in hlo_text.splitlines():
        if m := _INSTR.match(line):
            name, rhs = m[1], m[2]
            op = _OP_NAME.search(rhs)
            own[name] = scope_of_op_name(op[1], prefix) if op else None
            called = _CALLS.findall(rhs)
            if b := _BRANCHES.search(rhs):
                called += [c.strip().lstrip("%") for c in b[1].split(",")]
            if called:
                callees[name] = called
            operands[name] = _OPERAND.findall(rhs)
            if computation is not None:
                members[computation].append(name)
        elif m := _COMPUTATION.match(line):
            computation = m[1]
        elif line.startswith("}"):
            computation = None

    votes_of: dict[str, Counter] = {}

    def votes(comp: str, seen: frozenset) -> Counter:
        if comp in votes_of:
            return votes_of[comp]
        tally: Counter = Counter()
        for name in members.get(comp, ()):
            if own[name] is not None:
                tally[own[name]] += 1
            for c in callees.get(name, ()):
                if c not in seen:
                    tally += votes(c, seen | {c})
        votes_of[comp] = tally
        return tally

    out: dict[str, tuple[str, str]] = {}
    for name, scope in own.items():
        if scope is not None:
            out[name] = (scope, "own")
        elif name in callees:
            tally: Counter = Counter()
            for c in callees[name]:
                tally += votes(c, frozenset((c,)))
            if tally:
                out[name] = (tally.most_common(1)[0][0], "called")
    for names in members.values():
        local = set(names)
        users: dict[str, list[str]] = defaultdict(list)
        for name in names:
            for o in operands[name]:
                if o in local:
                    users[o].append(name)
        todo = [n for n in names if n not in out]
        while todo:
            found = {}
            for name in todo:
                for near in (users[name],
                             [o for o in operands[name] if o in local]):
                    tally = Counter(out[n][0] for n in near if n in out)
                    if tally:
                        found[name] = (tally.most_common(1)[0][0],
                                       "neighbour")
                        break
            if not found:
                break
            out.update(found)
            todo = [n for n in todo if n not in found]
    return out


def instruction_name(event_name: str) -> str:
    """The trace names an operation by its whole HLO line."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


# ---------------------------------------------------------------------------
# intervals
# ---------------------------------------------------------------------------

def own_events(ops) -> list[tuple[str, float, float]]:
    """[(name, start ns, own ns)] per event: its duration less what the
    events nested in it cover."""
    out: list[tuple[str, float, float]] = []
    stack: list[list] = []                 # [name, start, end, own_ns]
    for name, start, dur in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and start >= stack[-1][2]:
            done = stack.pop()
            out.append((done[0], done[1], done[3]))
        if stack:
            stack[-1][3] -= min(dur, stack[-1][2] - start)
        stack.append([name, start, start + dur, dur])
    out += [(d[0], d[1], d[3]) for d in stack]
    return out


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(a, b) for a, b in out]


def innermost_segments(spans) -> list[tuple[float, float, str]]:
    """The main thread's time cut where a span opens or closes, each
    piece named by the innermost span open in it (spans of one thread
    nest). Time under no span is left out."""
    edges: list[tuple[float, int, int, str]] = []
    for k, (name, start, dur) in enumerate(
            sorted(spans, key=lambda s: (s[1], -s[2]))):
        edges.append((start, 1, k, name))
        edges.append((start + dur, 0, k, name))
    edges.sort(key=lambda e: (e[0], e[1], e[2] if e[1] else -e[2]))
    out: list[tuple[float, float, str]] = []
    stack: list[tuple[int, str]] = []
    last = None
    for t, opens, k, name in edges:
        if stack and last is not None and t > last:
            out.append((last, t, stack[-1][1]))
        if opens:
            stack.append((k, name))
        else:
            stack = [s for s in stack if s[0] != k]
        last = t
    return out


def _overlaps(segments, starts, lo: float, hi: float):
    """(name, overlap) of [lo, hi) with each sorted, disjoint segment."""
    k = max(0, bisect.bisect_right(starts, lo) - 1)
    while k < len(segments) and segments[k][0] < hi:
        a, b, name = segments[k]
        if min(b, hi) > max(a, lo):
            yield name, min(b, hi) - max(a, lo)
        k += 1


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

def reduce(profile: dict, root: str = ROOT,
           prefix: str | tuple[str, ...] = SCOPE_PREFIX,
           longest: int = 10) -> dict:
    """See the module docstring. Seconds throughout. -> {"window_s",
    "jobs": [{"start_s", "wall_s", "devices": {chip: {"busy_s",
    "idle_s", "scopes": {scope: s}, "by_rule": {rule: s},
    "idle_by_span": {span: s}}}}], "per_job": the same five, mean over
    jobs and chips, "between_jobs_s": idle seconds outside every root
    span, mean over chips, "longest_gaps": [{"s", "chip", "parts":
    {span: s}}]}. `by_rule` says how the scoped seconds came by their
    scope (`module_scopes`)."""
    roots = sorted((s, s + d) for n, s, d in profile["spans"] if n == root)
    if not roots:
        raise ValueError(f"the profile holds no {root!r} span: was the "
                         "trace taken around run_train, by a tracer with "
                         "device=True?")
    if not profile["devices"]:
        raise ValueError("the profile holds no /device:TPU plane")
    lo, hi = roots[0][0], roots[-1][1]
    segments = innermost_segments(profile["spans"])
    seg_starts = [s[0] for s in segments]
    root_starts = [r[0] for r in roots]
    scopes_by_module = {name: module_scopes(text, prefix)
                        for name, text in profile["hlo"].items()}

    def job_of(t: float) -> int | None:
        k = bisect.bisect_right(root_starts, t) - 1
        return k if k >= 0 and t < roots[k][1] else None

    def by_span(a: float, b: float) -> dict[str, float]:
        parts: dict[str, float] = defaultdict(float)
        for span, ns in _overlaps(segments, seg_starts, a, b):
            parts[span] += ns / 1e9
        rest = (b - a) / 1e9 - sum(parts.values())
        if rest > 1e-12:
            parts[OUTSIDE] += rest
        return parts

    kinds = ("scopes", "by_rule", "idle_by_span")
    jobs = [{"start_s": (a - lo) / 1e9, "wall_s": (b - a) / 1e9,
             "devices": {}} for a, b in roots]
    between = 0.0
    gaps: list[tuple[float, float, str]] = []
    for chip, lines in profile["devices"].items():
        devs = []
        for job in jobs:
            devs.append({"busy_s": 0.0, "idle_s": 0.0,
                         **{kind: defaultdict(float) for kind in kinds}})
            job["devices"][chip] = devs[-1]
        modules = sorted((s, s + d, n) for n, s, d in lines["modules"])
        mod_starts = [m[0] for m in modules]
        # an operation belongs to the job it started in
        for name, start, own_ns in own_events(lines["ops"]):
            if (k := job_of(start)) is None:
                continue
            m = bisect.bisect_right(mod_starts, start) - 1
            scopes = (scopes_by_module.get(modules[m][2], {})
                      if m >= 0 and start < modules[m][1] else {})
            scope, rule = scopes.get(instruction_name(name),
                                     (UNSCOPED, UNSCOPED))
            devs[k]["scopes"][scope] += own_ns / 1e9
            devs[k]["by_rule"][rule] += own_ns / 1e9
        covered = [(max(a, lo), min(b, hi)) for a, b in union(
            (s, s + d) for _, s, d in lines["ops"])
            if min(b, hi) > max(a, lo)]
        for a, b in covered:
            if (k := job_of(a)) is not None:
                devs[k]["busy_s"] += (b - a) / 1e9
        edges = [lo] + [x for ab in covered for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            gaps.append((a, b, chip))
            inside = 0.0
            # cut the gap at the roots' edges: each job gets its part
            first = max(0, bisect.bisect_right(root_starts, a) - 1)
            for k in range(first, len(roots)):
                ra, rb = roots[k]
                if ra >= b:
                    break
                if rb <= a:
                    continue
                part = (max(a, ra), min(b, rb))
                inside += part[1] - part[0]
                devs[k]["idle_s"] += (part[1] - part[0]) / 1e9
                for span, sec in by_span(*part).items():
                    devs[k]["idle_by_span"][span] += sec
            between += (b - a - inside) / 1e9 / len(profile["devices"])

    n = len(jobs) * len(profile["devices"])
    per_job: dict = {"busy_s": 0.0, "idle_s": 0.0,
                     **{kind: defaultdict(float) for kind in kinds}}
    for job in jobs:
        for dev in job["devices"].values():
            per_job["busy_s"] += dev["busy_s"] / n
            per_job["idle_s"] += dev["idle_s"] / n
            for kind in kinds:
                dev[kind] = dict(dev[kind])
                for name, sec in dev[kind].items():
                    per_job[kind][name] += sec / n
    for kind in kinds:
        per_job[kind] = dict(per_job[kind])
    gaps.sort(key=lambda g: g[0] - g[1])
    return {"window_s": (hi - lo) / 1e9, "jobs": jobs, "per_job": per_job,
            "between_jobs_s": between,
            "longest_gaps": [{"s": (b - a) / 1e9, "chip": chip,
                              "parts": dict(by_span(a, b))}
                             for a, b, chip in gaps[:longest]]}


def operations(profile: dict, scope: str,
               prefix: str | tuple[str, ...] = SCOPE_PREFIX) -> list[dict]:
    """The operations behind one scope's seconds: every instruction
    whose scope path has `scope` as an element, over the whole trace and
    every chip -> [{"scope", "rule", "op": the HLO line (its result's
    shape, what it reads), "s": own seconds, "calls"}], longest first.
    What `reduce` sums, before the sum: which fusions, copies and
    kernels a scope's time is, and how each came by the scope."""
    scopes_by_module = {name: module_scopes(text, prefix)
                        for name, text in profile["hlo"].items()}
    rows: dict[tuple, list] = defaultdict(lambda: [0.0, 0])
    for lines in profile["devices"].values():
        modules = sorted((s, s + d, n) for n, s, d in lines["modules"])
        mod_starts = [m[0] for m in modules]
        for name, start, own_ns in own_events(lines["ops"]):
            m = bisect.bisect_right(mod_starts, start) - 1
            if m < 0 or start >= modules[m][1]:
                continue
            path, rule = scopes_by_module.get(modules[m][2], {}).get(
                instruction_name(name), (UNSCOPED, UNSCOPED))
            if scope in path.split("/"):
                row = rows[path, rule, name]
                row[0] += own_ns / 1e9
                row[1] += 1
    return [{"scope": path, "rule": rule, "op": op, "s": sec, "calls": n}
            for (path, rule, op), (sec, n) in sorted(
                rows.items(), key=lambda kv: -kv[1][0])]


# ---------------------------------------------------------------------------
# the table
# ---------------------------------------------------------------------------

def render(result: dict) -> str:
    per = result["per_job"]
    chips = len(result["jobs"][0]["devices"])
    lines = [
        f"{len(result['jobs'])} job(s) on {chips} chip(s), window "
        f"{result['window_s']:.3f} s; per job and chip: wall "
        f"{sum(j['wall_s'] for j in result['jobs']) / len(result['jobs']):.3f}"
        f" s, device busy {per['busy_s']:.3f} s, idle {per['idle_s']:.3f} s",
        "", "device seconds by scope (own time of the chip's operations)"]
    busy = sum(per["scopes"].values()) or 1.0
    for name, sec in sorted(per["scopes"].items(), key=lambda x: -x[1]):
        lines.append(f"  {name:<34}{sec:>10.4f}  {100 * sec / busy:5.1f} %")
    lines.append("  scope from: " + ", ".join(
        f"{rule} {100 * per['by_rule'].get(rule, 0.0) / busy:.1f} %"
        for rule in (*RULES, UNSCOPED)))
    lines += ["", "device idle seconds by the innermost span open on the "
              "main thread"]
    idle = sum(per["idle_by_span"].values()) or 1.0
    for name, sec in sorted(per["idle_by_span"].items(),
                            key=lambda x: -x[1]):
        lines.append(f"  {name:<34}{sec:>10.4f}  {100 * sec / idle:5.1f} %")
    lines += ["", "longest idle gaps"]
    lines.insert(1, f"idle between jobs {result['between_jobs_s']:.3f} s "
                 "a chip in the whole window")
    for gap in result["longest_gaps"]:
        parts = ", ".join(f"{n} {s:.3f}" for n, s in sorted(
            gap["parts"].items(), key=lambda x: -x[1]))
        lines.append(f"  {gap['s']:>8.3f} s  {gap['chip']}: {parts}")
    return "\n".join(lines)


def main(argv: list[str]) -> int:
    if len(argv) not in (1, 3) or (len(argv) == 3 and argv[1] != "--ops"):
        print(__doc__.split("\n\n")[0] + "\n\n    python -m "
              "pio_tpu.obs.profile <profile dir or .xplane.pb> "
              "[--ops SCOPE]", file=sys.stderr)
        return 2
    profile = read_profile(argv[0])
    if len(argv) == 1:
        print(render(reduce(profile)))
        return 0
    rows = operations(profile, argv[2])
    print(f"{argv[2]}: {sum(r['s'] for r in rows):.4f} s over the trace, "
          f"{len(rows)} instructions")
    for r in rows:
        print(f"{r['s']:10.4f} s {r['calls']:6d} x  [{r['rule']}] "
              f"{r['scope']}  {r['op'][:240]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
