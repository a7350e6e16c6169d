"""`rehearsal` marks a test that starts `benchmarks/run.py`: it needs the
program and a minute or more, and reads a cell's result line, not the
document. The tier-1 run (`-m 'not slow'`) runs them all the same;
`test_benchmark_grown_tree.py` selects by the marker to leave them out."""


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "rehearsal: starts benchmarks/run.py; left out of the run over the "
        "grown tree (test_benchmark_grown_tree.py)")
