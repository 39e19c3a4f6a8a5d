"""Plain references, one module per model family, named by a
configuration file's ``family`` key."""
