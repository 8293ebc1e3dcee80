"""Signature-parity sweep: public apex entry points vs apex_tpu.

The reference package cannot be imported here (its __init__ pulls CUDA
extensions), so reference signatures are read via ``ast`` from the
source tree; apex_tpu signatures via ``inspect``. Output: a markdown
table (stdout) consumed by docs/migrating.md's parity section, with one
row per entry point and an explicit delta column. Run:

    JAX_PLATFORMS=cpu PYTHONPATH=. python scripts/api_parity.py
"""
import ast
import importlib
import inspect
import os

REF = os.environ.get("APEX_REF", "/root/reference/apex")

# (reference file, qualname, apex_tpu module, attr)
# qualname "Class.__init__" takes the __init__ args (minus self);
# bare "fn" takes the function args.
ENTRIES = [
    ("amp/frontend.py", "initialize", "apex_tpu.amp", "initialize"),
    ("amp/handle.py", "scale_loss", "apex_tpu.amp", "scale_loss"),
    ("amp/frontend.py", "state_dict", "apex_tpu.amp", "state_dict"),
    ("amp/frontend.py", "load_state_dict", "apex_tpu.amp",
     "load_state_dict"),
    ("amp/amp.py", "half_function", "apex_tpu.amp", "half_function"),
    ("amp/amp.py", "float_function", "apex_tpu.amp", "float_function"),
    ("amp/amp.py", "register_half_function", "apex_tpu.amp",
     "register_half_function"),
    ("optimizers/fused_adam.py", "FusedAdam.__init__",
     "apex_tpu.optimizers", "FusedAdam"),
    ("optimizers/fused_lamb.py", "FusedLAMB.__init__",
     "apex_tpu.optimizers", "FusedLAMB"),
    ("optimizers/fused_sgd.py", "FusedSGD.__init__",
     "apex_tpu.optimizers", "FusedSGD"),
    ("optimizers/fused_novograd.py", "FusedNovoGrad.__init__",
     "apex_tpu.optimizers", "FusedNovoGrad"),
    ("optimizers/fused_adagrad.py", "FusedAdagrad.__init__",
     "apex_tpu.optimizers", "FusedAdagrad"),
    ("parallel/LARC.py", "LARC.__init__", "apex_tpu.optimizers", "LARC"),
    ("normalization/fused_layer_norm.py", "FusedLayerNorm.__init__",
     "apex_tpu.normalization", "FusedLayerNorm"),
    ("normalization/fused_layer_norm.py", "MixedFusedLayerNorm.__init__",
     "apex_tpu.normalization", "MixedFusedLayerNorm"),
    ("parallel/distributed.py", "DistributedDataParallel.__init__",
     "apex_tpu.parallel", "DistributedDataParallel"),
    ("parallel/optimized_sync_batchnorm.py", "SyncBatchNorm.__init__",
     "apex_tpu.parallel", "SyncBatchNorm"),
    ("parallel/__init__.py", "convert_syncbn_model",
     "apex_tpu.parallel", "convert_syncbn_model"),
    ("fp16_utils/fp16util.py", "network_to_half", "apex_tpu.fp16_utils",
     "network_to_half"),
    ("fp16_utils/fp16_optimizer.py", "FP16_Optimizer.__init__",
     "apex_tpu.fp16_utils", "FP16_Optimizer"),
    ("fp16_utils/loss_scaler.py", "LossScaler.__init__",
     "apex_tpu.fp16_utils", "LossScaler"),
    ("multi_tensor_apply/multi_tensor_apply.py",
     "MultiTensorApply.__init__", "apex_tpu.multi_tensor_apply",
     "MultiTensorApply"),
    ("mlp/mlp.py", "MLP.__init__", "apex_tpu.mlp", "MLP"),
    ("fused_dense/fused_dense.py", "FusedDense.__init__",
     "apex_tpu.fused_dense", "FusedDense"),
    ("reparameterization/__init__.py", "apply_weight_norm",
     "apex_tpu.reparameterization", "apply_weight_norm"),
    ("transformer/tensor_parallel/layers.py",
     "ColumnParallelLinear.__init__",
     "apex_tpu.transformer.tensor_parallel", "ColumnParallelLinear"),
    ("transformer/tensor_parallel/layers.py",
     "RowParallelLinear.__init__",
     "apex_tpu.transformer.tensor_parallel", "RowParallelLinear"),
    ("transformer/tensor_parallel/layers.py",
     "VocabParallelEmbedding.__init__",
     "apex_tpu.transformer.tensor_parallel", "VocabParallelEmbedding"),
    ("transformer/parallel_state.py", "initialize_model_parallel",
     "apex_tpu.transformer.parallel_state", "initialize_model_parallel"),
    ("contrib/optimizers/distributed_fused_adam.py",
     "DistributedFusedAdam.__init__",
     "apex_tpu.contrib.optimizers", "DistributedFusedAdam"),
    ("contrib/optimizers/distributed_fused_lamb.py",
     "DistributedFusedLAMB.__init__",
     "apex_tpu.contrib.optimizers", "DistributedFusedLAMB"),
    ("contrib/sparsity/asp.py", "ASP.init_model_for_pruning",
     "apex_tpu.contrib.sparsity", "ASP"),
]


def ref_params(path, qualname):
    full = os.path.join(REF, path)
    if not os.path.exists(full):
        return None
    tree = ast.parse(open(full).read())
    parts = qualname.split(".")
    node = tree
    body = tree.body
    target = None
    if len(parts) == 2 and parts[1] == "__init__":
        for n in body:
            if isinstance(n, ast.ClassDef) and n.name == parts[0]:
                for m in n.body:
                    if isinstance(m, ast.FunctionDef) and m.name == "__init__":
                        target = m
    elif len(parts) == 2:
        for n in body:
            if isinstance(n, ast.ClassDef) and n.name == parts[0]:
                for m in n.body:
                    if isinstance(m, ast.FunctionDef) and m.name == parts[1]:
                        target = m
    else:
        for n in body:
            if isinstance(n, ast.FunctionDef) and n.name == parts[0]:
                target = n
    if target is None:
        return None
    a = target.args
    names = [x.arg for x in a.posonlyargs + a.args + a.kwonlyargs]
    if a.vararg:
        names.append("*" + a.vararg.arg)
    if a.kwarg:
        names.append("**" + a.kwarg.arg)
    return [n for n in names if n != "self"]


def tpu_params(module, attr):
    try:
        mod = importlib.import_module(module)
        obj = getattr(mod, attr)
    except Exception as exc:            # noqa: BLE001 — report as a row
        return None, f"import failed: {exc}"
    if inspect.isclass(obj):
        try:
            sig = inspect.signature(obj.__init__)
            names = [n for n in sig.parameters if n != "self"]
        except (TypeError, ValueError):
            return None, "no signature"
    else:
        try:
            sig = inspect.signature(obj)
            names = list(sig.parameters)
        except (TypeError, ValueError):
            return None, "no signature"
    return names, None


def main():
    rows = []
    for path, qual, module, attr in ENTRIES:
        rp = ref_params(path, qual)
        tp, err = tpu_params(module, attr)
        name = qual.replace(".__init__", "")
        if rp is None:
            rows.append((name, "ref not found", "", ""))
            continue
        if tp is None:
            rows.append((name, err, "", ""))
            continue
        rset, tset = set(rp), set(tp)
        missing = [p for p in rp if p not in tset
                   and not p.startswith("*")]
        extra = [p for p in tp if p not in rset and not p.startswith("*")]
        status = "match" if not missing else "delta"
        rows.append((name, status,
                     " ".join(missing) or "-", " ".join(extra) or "-"))
    print("| entry point | status | ref-only params | tpu-only params |")
    print("|---|---|---|---|")
    for r in rows:
        print("| `%s` | %s | %s | %s |" % r)


if __name__ == "__main__":
    main()
