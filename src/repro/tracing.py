"""The names a trace of this program carries, and the two ways to set them.

``scope(name)`` is ``jax.named_scope``: compile-time metadata only. Every
HLO instruction traced inside it carries the name in its ``op_name``
(backward ops too, as ``transpose(jvp(<name>))``), so a device trace can
be split by the layer that issued each op. ``span(name)`` is
``jax.profiler.TraceAnnotation``: a host interval in a profiler trace,
costing well under a microsecond when no trace is being recorded.

Every name starts with ``db.``; the benchmark's own spans are ``bench.*``.

Device scopes (the training and serving programs):

- ``BLOCK_VIEW``: a block's slice of the stacked parameters, its cast to
  the compute dtype, and the write-back of the updated slice;
- ``NOISE``: sigma and epsilon, the embedding lookup, preconditioning and
  the sigma conditioning of the noisy stream;
- ``LAYERS``: the scan over a block's layers (its per-layer slicing and
  stacking), around the layers' own scopes;
- ``ATTN``, ``MLP``, ``ADALN``: inside each transformer layer (``ADALN``
  holds the norms, the modulation and the gated residuals);
- ``READOUT_CE``: the denoiser combine and the chunked CE readout, or the
  EDM loss;
- ``OPTIMIZER``: gradient clipping, AdamW and the parameter update, for
  the block and for the periphery;
- ``PSUM``: the periphery gradients' masked sum and all-reduce;
- ``GUARD``: the anomaly guard's verdict and the selects it drives;
- ``PROBE``, ``COMMIT``, ``SAMPLE``: serving's denoising probes through
  the blocks, the commit of a token (or a prompt chunk) into every
  layer's cache, and the readout plus sampling.

Host spans (the program's loops):

- ``PLACE``, ``DISPATCH``, ``GUARD_SYNC``: one block-parallel batch — its
  inputs placed on the mesh, the step dispatched, the guard verdicts read
  back to the host;
- ``BATCH``, ``LOSS_READBACK``: one iteration of the sequential loop —
  the batch and block drawn, the loss read back (``DISPATCH`` between);
- ``ADMIT``, ``COW``, ``RETIRE``: the continuous batcher's admission,
  copy-on-write of shared pages, and retirement of finished slots.
"""
from __future__ import annotations

import jax

# device scopes
BLOCK_VIEW = "db.block_view"
NOISE = "db.noise"
LAYERS = "db.layers"
ATTN = "db.attn"
MLP = "db.mlp"
ADALN = "db.adaln"
READOUT_CE = "db.readout_ce"
OPTIMIZER = "db.optimizer"
PSUM = "db.psum"
GUARD = "db.guard"
PROBE = "db.probe"
COMMIT = "db.commit"
SAMPLE = "db.sample"

# host spans
PLACE = "db.place"
DISPATCH = "db.dispatch"
GUARD_SYNC = "db.guard_sync"
BATCH = "db.batch"
LOSS_READBACK = "db.loss_readback"
ADMIT = "db.admit"
COW = "db.cow"
RETIRE = "db.retire"


def scope(name: str):
    """Name the device ops traced inside the block (``jax.named_scope``)."""
    return jax.named_scope(name)


def span(name: str):
    """Mark a host interval for the profiler (``TraceAnnotation``)."""
    return jax.profiler.TraceAnnotation(name)
