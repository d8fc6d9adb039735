"""Model zoo: pure-pytree implementations of the assigned families."""
from repro.models.layers import QuantCtx
from repro.models.model_zoo import (
    ModelApi,
    build_model,
    init_and_quantize,
    input_specs,
    load_servable,
    make_ctx,
    make_smoke_batch,
    quantize_and_plan,
    quantize_model_params,
    save_servable,
)
