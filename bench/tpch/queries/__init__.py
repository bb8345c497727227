"""One TPC-H query per module, found by its name (``q1`` -> ``q1.py``).

Each module gives ``COLUMNS``, the program's ``QueryPlan`` as ``plan()``,
``reference(cols)`` (float64 NumPy answer), ``read(result)`` (the fused
result in the reference's form), ``control(cols, dtype)`` (the reference
computed in a lower precision with ``jax.numpy``, in the fused result's
form) and ``rel_error(got, want)``.
"""
