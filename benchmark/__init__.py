"""The benchmark of the served query path (see README.md beside this file)."""
