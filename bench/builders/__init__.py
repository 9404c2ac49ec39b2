"""One builder per kind of system under test, named by the
configuration's ``system`` key: ``bench/builders/<system>.py`` exports
``build(cfg, seed) -> bench.systems.System``.
"""
