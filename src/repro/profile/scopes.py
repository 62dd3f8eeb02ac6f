"""Names of the training step's parts, as they appear in a device trace.

The step wraps each part in ``jax.named_scope(<name>)``. A named scope is
HLO metadata only: it adds no operation and costs nothing at run time,
but every device op the part produces carries the name in its
``op_name`` (the profiler's ``tf_op``), on the same clock as the trace.
Autodiff adds its own wrappers around them: ``jvp(...)`` for the
forward, ``transpose(jvp(...))`` for the backward, and
``rematted_computation`` for the recompute under ``jax.checkpoint``.

The host side of :func:`repro.train.loop.run_training` is named by
``jax.profiler.TraceAnnotation`` spans (``SPAN_*``), which show on the
host plane of the same trace. ``docs/profiling.md`` says how to capture
one.
"""

# the step (repro.train.step.make_train_step)
MODEL = "model"                # forward logits and the loss
TRANSPORT = "transport"        # gradient transport: prepare, reduce, finalize
OPTIMIZER = "optimizer"        # the whole optimizer update
STEP_METRICS = "step_metrics"  # the step's reported gradient norm

# inside MODEL
EMBED = "embed"
ATTENTION = "attention"
MLP = "mlp"
NORM = "norm"
HEAD = "head"                  # final norm and the vocabulary projection
LOSS = "loss"

# inside MLP, for an expert layer (moe_strategy="dropless")
ROUTER = "router"              # router logits, softmax, top-k, its loss
DISPATCH = "dispatch"          # sort the assignments held here by expert
EXPERTS = "experts"            # the grouped matmuls of the held experts
COMBINE = "combine"            # unsort, and sum each token's gated outputs

# inside OPTIMIZER
SR_BITS = "sr_bits"            # random bits for stochastic rounding
LAYOUT = "layout"              # a flat-path leaf's copy into tiles and back
FUSED_ADAMW = "fused_adamw"    # the Pallas kernels' own names
FUSED_SGD = "fused_sgd"

STEP_SCOPES = (MODEL, TRANSPORT, OPTIMIZER, STEP_METRICS)
MODEL_SCOPES = (EMBED, ATTENTION, MLP, NORM, HEAD, LOSS)
MLP_SCOPES = (ROUTER, DISPATCH, EXPERTS, COMBINE)
OPTIMIZER_SCOPES = (SR_BITS, LAYOUT)

# host spans of run_training's loop, one per part of a step
SPAN_BATCH = "train.batch"            # fetch the next batch
SPAN_DISPATCH = "train.dispatch"      # dispatch the jitted step
SPAN_SYNC = "train.sync"              # wait for the step's loss
SPAN_FLUSH = "train.flush"            # write the metrics out
SPAN_CHECKPOINT = "train.checkpoint"  # save or commit a checkpoint

LOOP_SPANS = (SPAN_BATCH, SPAN_DISPATCH, SPAN_SYNC, SPAN_FLUSH,
              SPAN_CHECKPOINT)
