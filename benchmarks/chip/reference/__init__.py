"""Plain float32 references. They import nothing of ``src/repro``."""
