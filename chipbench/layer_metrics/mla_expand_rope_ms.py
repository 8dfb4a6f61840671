"""Layer: functional trainers. Milliseconds of device time a step that the
rotary latent-attention mixers spend between their projections and their
core: the scopes ``mla_expand`` (the latent's RMS norm, the expansion by
``W_kvb`` into a head's key channels and value, the assembly of the keys with
the shared one copied to every head) and ``rope`` (the decoupled 64 channels
of every query head and the one shared key turned in interleaved pairs,
inside ``attention``), forward, the recomputed forward and backward together.
But for the expansion's matmul these are passes over [positions, 32 heads x
192], bound by bytes. None where the trace lacks either scope."""

from chipbench import scope_profile


def metric(facts):
    parts = [scope_profile.ms(facts, "scope_ns", scope, "total")
             for scope in ("mla_expand", "rope")]
    return None if None in parts else sum(parts)
