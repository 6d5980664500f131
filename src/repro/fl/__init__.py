from repro.fl.adapters import (EvalResult, LMAdapter, MLPAdapter, ModelAdapter,
                               finch_adapter, make_adapter, rwkv6_adapter,
                               transformer_adapter)
from repro.fl.batched_fel import (BatchedFELEngine, BatchedTrainSpec,
                                  engine_for)
from repro.fl.client import Client, local_train
from repro.fl.fedavg import fedavg
from repro.fl.hierarchy import FELCluster, build_hierarchy
from repro.fl.hfl_runtime import (AllNodesPlagiarizeError, BHFLConfig,
                                  BHFLRuntime, RoundMetrics)

__all__ = ["Client", "local_train", "fedavg", "FELCluster", "build_hierarchy",
           "BHFLConfig", "BHFLRuntime", "RoundMetrics",
           "AllNodesPlagiarizeError",
           "BatchedFELEngine", "BatchedTrainSpec", "engine_for",
           "ModelAdapter", "MLPAdapter", "LMAdapter", "EvalResult",
           "make_adapter", "transformer_adapter", "rwkv6_adapter",
           "finch_adapter"]
