"""Device-idle time inside the batcher's ``serve.admit``,
``serve.build`` and ``serve.upload`` spans (the wave rebuilt before each
launch), per traced run() call, in ms."""
from bench.core.scopes import wave_gap_ms as read  # noqa: F401
