"""``tier1_cases.py``, the list a tier-1 file collects, against the files
it names: ``JAX_PLATFORMS=cpu python -m pytest
benchmark/tests/test_tier1_cases.py -q``."""

import importlib


def test_every_name_is_a_case_of_the_files_and_none_is_listed_twice():
    listed = importlib.import_module("benchmark.tests.tier1_cases")
    cases = [getattr(listed, name) for name in listed.__all__]
    assert len(cases) >= 38     # 28 names at PR 52 (88 cases); 38 (117) at PR 62
    for case in cases:
        module = case.__module__.rpartition(".")[2]
        assert module in listed.MODULES, case
        assert case.__name__.startswith("test_"), case
    assert len({(c.__module__, c.__name__) for c in cases}) == len(cases)
    # what ``from benchmark.tests.tier1_cases import *`` hands a tier-1 file
    star = {}
    exec("from benchmark.tests.tier1_cases import *", star)
    assert {n for n in star if n.startswith("test_")} == set(listed.__all__)
