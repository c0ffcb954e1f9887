"""The harness tests' scratch checkout (``tests/test_bench_run.py``'s
``checkout`` fixture) holds tiny copies of the configurations it was
written for; a configuration of a kind added since gets its own tiny copy
written beside them, by the module that tests that kind, so that every
cell of ``BENCHMARK.json`` runs there at a CPU size."""
import pytest


@pytest.hookimpl(hookwrapper=True)
def pytest_fixture_setup(fixturedef, request):
    outcome = yield
    if (fixturedef.argname == "checkout" and outcome.excinfo is None
            and request.module.__name__ == "test_bench_run"):
        from test_bench_vlm import write_tiny_vlm
        write_tiny_vlm(outcome.get_result())
