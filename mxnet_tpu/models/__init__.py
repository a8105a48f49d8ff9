from . import transformer  # noqa: F401
from .transformer import (  # noqa: F401
    MultiHeadAttention, TransformerEncoderLayer, TransformerLM, BERTModel,
    tensor_parallel_shardings,
)
from . import laguna  # noqa: F401
from .laguna import (  # noqa: F401
    RMSNorm, LagunaAttention, LagunaDecoderLayer, LMHead, LagunaLM,
)
