"""TransmogrifAI-TPU: a TPU-native AutoML framework for structured data.

A ground-up rebuild of the capabilities of TransmogrifAI (Salesforce's
Scala/Spark AutoML library) designed for TPUs: typed feature pipelines compile
to XLA programs, automated feature engineering/validation run as device
reductions over an HBM-resident feature matrix, and the model-selection
cross-validation sweep runs as vmapped/sharded JAX programs over a device
mesh (batch x fold x grid axes) instead of a Spark cluster.

Public API mirrors the reference's (OpWorkflow, FeatureBuilder,
Transmogrifier, SanityChecker, ModelSelectors, evaluators) so a reference
user can switch with minimal relearning.
"""
from __future__ import annotations

import time as _time

_T0 = _time.time()   # the start-up ledger's t0 (utils/tracing.tracker)

__version__ = "0.4.0"

# Persistent XLA compilation cache: cold processes (examples, CI, serving
# starts, one chip call's processes) stop re-paying every compile. Placed
# by JAX_COMPILATION_CACHE_DIR, else TMOG_COMPILE_CACHE_DIR, else a fixed
# directory in the checkout; see utils/platform.enable_compilation_cache.
from .utils.platform import enable_compilation_cache as _ecc
_ecc()

# The process's one compile listener, always on: every trace, lowering,
# cache load and compile from here on is in platform.startup_record().
from .utils.tracing import tracker as _tracker
_tracker.install()

from . import types
from .types import *  # noqa: F401,F403 — feature type hierarchy
from .features.feature import Feature, FeatureHandle, FeatureHistory
from .features.builder import FeatureBuilder, infer_feature_type
from .features.generator import FeatureGeneratorStage
from .stages.base import (
    Estimator,
    JaxTransformer,
    LambdaTransformer,
    PipelineStage,
    Transformer,
    binary_transformer,
    unary_transformer,
)
from .stages.params import Param, ParamMap, param_grid
from .data.dataset import Column, Dataset, column_from_values
from .data.vector import VectorColumnMetadata, VectorMetadata
from . import dsl  # installs rich feature syntax (reference dsl/ implicits)

__all__ = [n for n in dir() if not n.startswith("_")]

# jax's Pallas modules, imported on a thread while the process goes on to
# reach its device (a TPU host with the kernels on; no thread elsewhere).
from .utils.platform import prefetch_kernel_modules as _pkm
_pkm()

_tracker.mark_import(_T0, _time.time())   # t1: keep this statement last
