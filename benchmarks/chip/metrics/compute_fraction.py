"""Share of branch evaluations the cache policy let run, in percent, from
``engine.report()``."""


def read(run):
    f = run.report.get("compute_fraction")
    return None if f is None else 100.0 * f
