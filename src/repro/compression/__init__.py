from repro.compression.pipeline import (BACKENDS, Backend,  # noqa: F401
                                        ExchangePipeline, LatticeWire,
                                        get_backend, wrap_gamma)
from repro.compression.codecs import (Codec, GroupedLatticeCodec,  # noqa: F401
                                      IdentityCodec, LatticeCodec,
                                      LatticeMsg, ScalarCodec, TopKEFCodec,
                                      is_lattice_family, make_codec,
                                      register_codec, registered_codecs,
                                      resolve_codec)
from repro.compression.transports import (Transport,  # noqa: F401
                                          make_transport,
                                          register_transport,
                                          registered_transports,
                                          transport_for_mode)
from repro.compression.rotation import rotate, pad_len  # noqa: F401
