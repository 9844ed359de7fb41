from .framing import encode_frame, decode_frame, read_frame, write_frame, FrameError  # noqa: F401
from .tracing import RequestTrace, new_request_id  # noqa: F401
