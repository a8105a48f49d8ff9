from . import transformer  # noqa: F401
from .transformer import (  # noqa: F401
    MultiHeadAttention, TransformerEncoderLayer, TransformerLM, BERTModel,
    tensor_parallel_shardings,
)
from . import laguna  # noqa: F401
from .laguna import (  # noqa: F401
    RMSNorm, LagunaAttention, LagunaDecoderLayer, LMHead, LagunaLM,
)
from . import lfm2  # noqa: F401
from .lfm2 import (  # noqa: F401
    Lfm2ShortConv, Lfm2Attention, Lfm2DecoderLayer, Lfm2MoeLM,
)
from . import ling  # noqa: F401
from .ling import (  # noqa: F401
    LingKDA, LingLatentAttention, LingDecoderLayer, LingHybridLM,
)
from . import nemotron_h  # noqa: F401
from .nemotron_h import (  # noqa: F401
    NemotronHMamba, NemotronHLM,
)
from . import keye  # noqa: F401
from .keye import (  # noqa: F401
    KeyeIndexer, KeyeAttention, KeyeLM,
)
