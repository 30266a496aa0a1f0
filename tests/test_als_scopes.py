"""Named scopes in the ALS device program (ops/als.py, ops/als_pallas.py):
every phase of a sweep is a `jax.named_scope` under `als.user` /
`als.item`, the names reach the lowered and the compiled text, and they
change nothing but metadata: with the scopes patched to no-ops the
optimized HLO is the same program."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import source_info_util
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pio_tpu.obs import profile
from pio_tpu.ops import als
from pio_tpu.parallel.mesh import DATA_AXIS

N_USERS, N_ITEMS, NNZ, RANK = 64, 48, 768, 8
PHASES = ("als.layout", "als.gather", "als.blocks", "als.flush", "als.gram")


def _params(cg_iters: int) -> als.ALSParams:
    # the accumulation the chip runs (XLA blocks + the Pallas flush, in
    # interpret mode here), two scans: one full-strength sweep, one warm
    return als.ALSParams(
        rank=RANK, iterations=2, reg=0.05, alpha=10.0, implicit=True,
        accum="hybrid", cg_iters=cg_iters, cg_warm_iters=1,
        cg_warm_sweeps=1, chunk=256, chunk_slots=128)


def _one_chip(cg_iters: int):
    params = _params(cg_iters)
    i32 = jax.ShapeDtypeStruct((NNZ,), jnp.int32)
    return als._train_jit.lower(
        i32, i32, jax.ShapeDtypeStruct((NNZ,), jnp.float32),
        n_users=N_USERS, n_items=N_ITEMS, params=params,
        user0=jax.ShapeDtypeStruct((N_USERS, RANK), jnp.float32),
        item0=jax.ShapeDtypeStruct((N_ITEMS, RANK), jnp.float32))


def _sharded(cg_iters: int):
    n_dev = 4
    mesh = Mesh(np.array(jax.devices()[:n_dev]), (DATA_AXIS,))
    params = _params(cg_iters)
    ub, ib = als._block(N_USERS, n_dev), als._block(N_ITEMS, n_dev)
    nnz = 256
    cs, su, si = als._slot_counts(nnz, nnz, ub, ib, params)
    als._sharded_train_fn.cache_clear()
    run = als._sharded_train_fn(mesh, ub, ib, su, si, cs, params)
    sh = NamedSharding(mesh, P(DATA_AXIS))

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    coo = [arg((n_dev, nnz), jnp.int32), arg((n_dev, nnz), jnp.int32),
           arg((n_dev, nnz), jnp.float32)]
    return run.lower(*coo, *coo, arg((n_dev, ub, RANK), jnp.float32),
                     arg((n_dev, ib, RANK), jnp.float32))


CASES = {
    "one-chip-cg": (_one_chip, 3, PHASES + ("als.cg",)),
    "one-chip-cholesky": (_one_chip, 0, PHASES + ("als.chol",)),
    "sharded-cg": (_sharded, 3, PHASES + ("als.cg", "als.all_gather")),
}


def _scopes_in(text: str) -> set[str]:
    """`als.user/als.gather` for every name-stack path in the text: its
    components that are scopes (`while/body/...` may lie between)."""
    found = {profile.scope_of_op_name(path) for path in re.findall(
        r"[\w.()/\-]*als\.(?!py\b)[\w.()/\-]*", text)}
    return found - {None}


def _program_only(hlo: str) -> str:
    """Optimized HLO less what a name may change: per-instruction
    metadata and the file/function/location tables before the first
    computation."""
    hlo = re.sub(r",?\s*metadata=\{[^}]*\}", "", hlo)
    head, sep, rest = hlo.partition("\n\n")
    if "FileNames" in rest.split("\n\n", 1)[0]:
        # module header, tables (one block each), then the computations
        blocks = rest.split("\n\n")
        while blocks and blocks[0].split("\n", 1)[0].strip() in (
                "FileNames", "FunctionNames", "FileLocations",
                "StackFrames"):
            blocks.pop(0)
        rest = "\n\n".join(blocks)
    return head + sep + rest


@pytest.fixture()
def no_compile_cache():
    """jax's persistent cache keys a program without its metadata, so
    the second compile below would come back as the first one's text."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("case", CASES)
def test_scopes_reach_the_text_and_change_only_metadata(
        case, monkeypatch, no_compile_cache):
    lower, cg_iters, phases = CASES[case]
    want = {f"{side}/{phase}" for side in ("als.user", "als.item")
            for phase in phases}
    jax.clear_caches()
    lowered = lower(cg_iters)
    named = lowered.compile().as_text()
    # the lowered text names a function called from both sides (a scan
    # body) once, by its own part of the path; the compiled text has
    # the whole path on every instruction
    in_lowered = {part for path in _scopes_in(
        lowered.as_text(debug_info=True)) for part in path.split("/")}
    assert {"als.user", "als.item", *phases} <= in_lowered
    assert want <= _scopes_in(named)

    real_enter = source_info_util.ExtendNameStackContextManager.__enter__

    def enter_unless_als(self):
        if self.name.startswith("als."):
            self.prev = source_info_util._source_info_context.context
            return self.prev.name_stack
        return real_enter(self)

    monkeypatch.setattr(source_info_util.ExtendNameStackContextManager,
                        "__enter__", enter_unless_als)
    jax.clear_caches()
    bare = lower(cg_iters).compile().as_text()
    assert not _scopes_in(bare)
    assert _program_only(bare) == _program_only(named)
    jax.clear_caches()


def consecutive_rule(op_name: str) -> str | None:
    """`scope_of_op_name` as it was before the sequence stack nested its
    scopes (PR 33): a scope counted once only where it repeated at
    once."""
    parts: list[str] = []
    for found in profile._scope_pattern(profile.SCOPE_PREFIX).findall(
            op_name):
        if not parts or parts[-1] != found:
            parts.append(found)
    return "/".join(parts) or None


@pytest.mark.parametrize("case", CASES)
def test_the_repeated_scope_rule_moves_only_joined_names_of_one_scope(case):
    """A scope that comes again now takes the path back to where it
    first stood (the prediction module's `seq.mtp/...` under an inlined
    helper). In an ALS program that moves only a fused operation whose
    name joins several paths of ONE scope with `;`: the older rule read
    `als.item/als.flush;als.item/als.flush` as a scope four deep that
    nothing asks for, this one as `als.item/als.flush`. Every other
    operation keeps its scope. (No benchmark metric reads an ALS scope:
    theirs come from benchmark/harness/trace.py.)"""
    lower, cg_iters, _ = CASES[case]
    names = set(profile._OP_NAME.findall(lower(cg_iters).compile().as_text()))
    assert len(names) > 100
    moved = {n for n in names
             if profile.scope_of_op_name(n) != consecutive_rule(n)}
    assert len(moved) < len(names) // 10
    for name in moved:
        parts = {consecutive_rule(part) for part in name.split(";")}
        assert len(name.split(";")) > 1 and len(parts) == 1
        assert profile.scope_of_op_name(name) == parts.pop()


def _entry_instructions(hlo: str) -> list[str]:
    """Names of the entry computation's instructions: what a trace shows
    as operations when the module has no loop (a fusion's inside runs as
    the fusion)."""
    entry = hlo[hlo.index("\nENTRY "):]
    return [m[1] for line in entry[:entry.index("\n}")].splitlines()
            if (m := profile._INSTR.match(line))]


def test_every_layout_op_carries_the_layout_scope(no_compile_cache):
    """`obs/profile.py` sums a trace's operations by the scope of their
    instruction: alone in a module, every instruction of the slot layout
    that runs as an operation (the sort, the row gathers, the shifts)
    resolves to `als.layout`, so the layout's seconds stay the layer's."""
    nnz, width = 4096, 8
    slots = als._slots_for(nnz, N_USERS, width, 128)

    def side(u, o, v):
        with jax.named_scope("als.user"):
            return als._device_slot_layout(u, o, v, N_USERS, width, slots)

    i32 = jax.ShapeDtypeStruct((nnz,), jnp.int32)
    jax.clear_caches()
    text = jax.jit(side).lower(
        i32, i32, jax.ShapeDtypeStruct((nnz,), jnp.float32)
    ).compile().as_text()
    assert " while(" not in text and " conditional(" not in text
    scopes = profile.module_scopes(text)
    names = _entry_instructions(text)
    assert len(names) > 20
    assert {scopes.get(name, (None,))[0] for name in names} == {
        "als.user/als.layout"}
    jax.clear_caches()
