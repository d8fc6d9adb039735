"""Plain float32 references, one module per model family, named by the
``reference`` key of a configuration file."""
