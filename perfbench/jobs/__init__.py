"""One module per job kind: ``setup(cell, seed, device)``, ``run(state,
seconds, tracer)``, ``check(state, result)``, ``teardown(state)``."""
