from repro.kernels.latent_attention.ops import latent_paged_decode_attention

__all__ = ["latent_paged_decode_attention"]
