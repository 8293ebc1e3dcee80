"""apex_tpu — a TPU-native training utility framework.

A from-scratch JAX/XLA/Pallas re-design of the capabilities of NVIDIA apex
(reference: /root/reference — mixed precision, fused optimizers/layers,
NCCL data-parallel utilities and Megatron-style model parallelism), built
idiomatically for TPU:

- ``apex_tpu.amp``       — O0–O3 mixed-precision policies, dynamic loss
  scaling, master weights (reference: ``apex/amp/frontend.py:100-191``),
  targeting bfloat16-on-XLA first, float16 supported for parity.
- ``apex_tpu.optimizers`` — fused multi-tensor optimizers (SGD, Adam(W),
  LAMB, NovoGrad, Adagrad) as single jitted flat-buffer updates
  (reference: ``csrc/amp_C_frontend.cpp:122-145``).
- ``apex_tpu.normalization`` / ``apex_tpu.fused_dense`` / ``apex_tpu.mlp``
  — fused layers lowered to Pallas kernels / XLA fusions
  (reference: ``csrc/layer_norm_cuda.cpp``, ``csrc/fused_dense.cpp``).
- ``apex_tpu.parallel``  — data-parallel gradient synchronization and
  synchronized BatchNorm over ICI collectives on a GSPMD mesh
  (reference: ``apex/parallel/distributed.py:129``).
- ``apex_tpu.zero``      — parameter-sharded (ZeRO-3/FSDP) training:
  regex sharding rules, gather-behind-forward / reduce-scatter-behind-
  backward, sharded fused Adam/LAMB with fp32 master shards under amp
  O2, elastic (world-size-changing) checkpoint resharding
  (reference: ``apex/contrib/optimizers/distributed_fused_adam.py``).
- ``apex_tpu.transformer`` — Megatron-style tensor/pipeline/sequence/
  context parallel state and layers mapped to TPU mesh axes
  (reference: ``apex/transformer/parallel_state.py:53``).
- ``apex_tpu.contrib``   — attention kernels (Pallas flash attention),
  fused cross entropy, transducer, group BN, sparsity
  (reference: ``apex/contrib/``).

Everything under a ``jax.jit`` is pure and functional; there is no
monkey-patching. Stateful convenience wrappers mirroring the apex object
API are thin shells over pure functions.
"""

__version__ = "0.1.0"

from apex_tpu import amp  # noqa: F401
from apex_tpu import multi_tensor_apply  # noqa: F401
from apex_tpu import optimizers  # noqa: F401
from apex_tpu import normalization  # noqa: F401
from apex_tpu import parallel  # noqa: F401
from apex_tpu import fused_dense  # noqa: F401
from apex_tpu import mlp  # noqa: F401
from apex_tpu import fp16_utils  # noqa: F401
from apex_tpu import reparameterization  # noqa: F401
from apex_tpu import rnn  # noqa: F401
from apex_tpu import monitor  # noqa: F401
from apex_tpu import checkpoint  # noqa: F401
from apex_tpu import zero  # noqa: F401
from apex_tpu import tune  # noqa: F401

# heavier subpackages (transformer, contrib, models) and the profiler
# shims over monitor's tool side (pyprof) import on demand:
#   import apex_tpu.transformer / .contrib / .models / .pyprof
RNN = rnn  # reference package name alias (apex.RNN)
