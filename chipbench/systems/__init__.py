"""The systems under test, one module per configuration kind, each run
through its public entry points."""
