from progen_tpu.models.progen import FeedForward, LocalAttention, ProGen, ProGenConfig, SGU

__all__ = ["FeedForward", "LocalAttention", "ProGen", "ProGenConfig", "SGU"]
