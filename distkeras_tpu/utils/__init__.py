from distkeras_tpu.utils.serialization import (
    serialize_keras_model,
    deserialize_keras_model,
)
from distkeras_tpu.utils.misc import to_dense_vector, uniform_weights
from distkeras_tpu.utils.profiling import StepTimer, trace

__all__ = [
    "serialize_keras_model",
    "deserialize_keras_model",
    "to_dense_vector",
    "uniform_weights",
    "StepTimer",
    "trace",
]
