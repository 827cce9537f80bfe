"""One module to an architecture, found by the ``family`` key of a
configuration file (``spec.family``).  What a family module holds is in
``llama.py``, the first, and in the README under "A family"."""
