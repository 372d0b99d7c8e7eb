from repro.codec.tvc import (  # noqa: F401
    CODEC_ALIASES,
    HEADER_PROBE_BYTES,
    TIERS,
    EncodedGOP,
    Tier,
    canonical_codec,
    decode_gop,
    deserialize_gop,
    encode_gop,
    gop_planes,
    is_compressed_codec,
    parse_gop_header,
    prefix_gop,
    serialize_gop,
    transcode_gop,
)
from repro.codec.gop import split_into_gops, UNCOMPRESSED_BLOCK_BYTES  # noqa: F401
