from analytics_zoo_tpu.ops.attention import (  # noqa: F401
    blockwise_attention,
    dot_product_attention,
    reference_attention,
)
from analytics_zoo_tpu.ops.dequant_matmul import (  # noqa: F401
    dequant_matmul,
    dequant_matmul_reference,
    pack_int4,
    quantize_weights,
    unpack_int4,
)
from analytics_zoo_tpu.ops.dispatch import select_path  # noqa: F401
from analytics_zoo_tpu.ops.embedding_bag import (  # noqa: F401
    embedding_bag,
    embedding_bag_reference,
)
from analytics_zoo_tpu.ops.flash_attention import flash_attention  # noqa: F401
from analytics_zoo_tpu.ops.quantization import (  # noqa: F401
    Calibrator,
    int8_dot,
    quantize_program,
    quantize_tensor,
)
from analytics_zoo_tpu.ops.ssm_scan import ssm_scan  # noqa: F401
# last: ring_attention pulls in analytics_zoo_tpu.parallel, whose
# modules import the ops submodules above — keep them initialized first
from analytics_zoo_tpu.ops.ring_attention import (  # noqa: F401,E402
    RING_MIN_LEN,
    ring_attention,
)
