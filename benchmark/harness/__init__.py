"""The benchmark's own code: nothing here is imported by the program, and the
harness process (``benchmark/run.py``) never imports JAX."""
