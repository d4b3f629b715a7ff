"""Names of the train step's device scopes.

Each differentiated loss body runs under ``jax.named_scope(MODEL)`` and
each optimizer update under ``jax.named_scope(OPTIMIZER)``.  The names
land only in the compiled step's HLO metadata (``op_name``): autodiff
names the forward's ops ``jvp(model)/...`` and the backward's
``transpose(jvp(model))/...``, the update's ``optimizer/...``.  A
profiler trace names device ops by their HLO instruction, so the op
names map every device-op interval to forward, backward or optimizer
(docs/observability.md, "Device scopes").  The benchmark reads these
names; they change only with it.
"""
MODEL = "model"
OPTIMIZER = "optimizer"
