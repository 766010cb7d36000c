"""The names of the program's profiler scopes and spans, defined once.

Device scopes (``jax.named_scope``) are trace-time metadata: they name
the HLO instructions of a compiled program (``op_name``, which a device
trace carries as each op's ``tf_op``) and change nothing it computes.
Host spans (``jax.profiler.TraceAnnotation``) record only while a
profiler runs and cost about a microsecond otherwise.  Both share the
profiler's clock with the device trace; nothing here keeps a record of
its own.  The benchmark's per-layer readers and ``PERF.md`` use these
same names.
"""
import jax

scope = jax.named_scope
span = jax.profiler.TraceAnnotation

# Device scopes of a layer program (core/engine.py, core/admm.py).
PROPAGATE = "dssfn.propagate"      # relu(W_l @ Y_{l-1})
GRAM = "dssfn.gram"                # Y Y^T + I/mu and T Y^T (the fused kernel too)
CHOLESKY = "dssfn.cholesky"        # the guarded Cholesky, retries included
ADMM = "dssfn.admm"                # the K-iteration scan
SOLVE = "admm.solve"               # right-hand side and cho_solve
MIX = "admm.mix"                   # the policy's consensus exchange
UPDATE = "admm.update"             # projection and dual step

# Host spans of the layer loop (core/layerwise.py).
LAYER = "dssfn.layer"              # arg ``layer``
DISPATCH = "dssfn.dispatch"        # enqueue of the layer program
HOST_SYNC = "dssfn.host_sync"      # a device -> host fetch
BUILD_WEIGHT = "dssfn.build_weight"

# Host spans of serving (serve/runtime.py).
SUBMIT = "serve.submit"            # arg ``request``: admission
LOCK_WAIT = "serve.lock_wait"      # arg ``request``: acquiring the runtime lock
FLUSH = "serve.flush"              # one pass over the queue
BATCH = "serve.batch"              # args ``first``, ``last``, ``requests``, ``samples``, ``bucket``
PACK = "serve.pack"                # concatenating the batch's requests
FORWARD = "serve.forward"          # pad, bucket program, block
SCATTER = "serve.scatter"          # results back to the handles
