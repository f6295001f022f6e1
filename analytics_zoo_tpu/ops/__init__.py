"""TPU kernels (Pallas) + attention dispatch.

The analog of the reference's native compute layer: where BigDL calls
MKL/MKL-DNN kernels behind every module (SURVEY.md section 2.4), the hot
ops here are Pallas TPU kernels with jnp fallbacks for CPU tracing/tests.
"""

from analytics_zoo_tpu.ops.attention import (  # noqa: F401
    dot_product_attention,
    packed_attention,
    reference_attention,
)
from analytics_zoo_tpu.ops.pallas_attention import (  # noqa: F401
    pallas_flash_attention_fwd,
)
