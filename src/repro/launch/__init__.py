"""repro.launch — mesh construction, train/serve launchers."""
