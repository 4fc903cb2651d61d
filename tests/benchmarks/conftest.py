"""What every test under ``tests/benchmarks/`` says of the entries of
``BENCHMARK.json`` it says twice: of the file as it is, and of a copy with a
later family appended (``benchmark_rehearsal.with_a_later_family``). A test
takes ``bench`` from here and reads no module-level copy of the file, so it
may say that its own entries exist and what they hold, and cannot say where
they stand, who else is in a list, or how many entries there are
(``benchmarks/README.md``, "What a family's test may say")."""

import json

import pytest

import benchmark_rehearsal as rehearsal

VARIANTS = {"as_it_is": lambda bench: bench,
            "with_a_later_family": rehearsal.with_a_later_family}


@pytest.fixture(params=sorted(VARIANTS))
def bench(request):
    return VARIANTS[request.param](
        json.loads((rehearsal.REPO / "BENCHMARK.json").read_text()))
