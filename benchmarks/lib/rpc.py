"""The program's own gRPC client (`seaweedfs_tpu.pb.rpc.Stub`), called from the
benchmark's synchronous code: one event loop in one thread for the whole run,
as a short-lived private loop would poison the client's cached channels.
Used for the calls chip_smoke.py makes the same way: shard unmount and delete."""

from __future__ import annotations

import asyncio
import os
import threading


class Rpc:
    def __init__(self):
        # the client's circuit breaker would turn a shed into a refusal to ask
        os.environ["SEAWEEDFS_TPU_BREAKER"] = "0"
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever, daemon=True, name="bench-rpc")
        self.thread.start()

    def call(self, hostport: str, service: str, method: str, request: dict, timeout: float = 60):
        from seaweedfs_tpu.pb import grpc_address
        from seaweedfs_tpu.pb.rpc import Stub

        async def go():
            return await Stub(grpc_address(hostport), service).call(method, request, timeout=timeout)

        reply = asyncio.run_coroutine_threadsafe(go(), self.loop).result(timeout + 5)
        if isinstance(reply, dict) and reply.get("error"):
            raise RuntimeError(f"{method}: {reply['error']}")
        return reply

    def close(self) -> None:
        from seaweedfs_tpu.pb.rpc import close_all_channels

        asyncio.run_coroutine_threadsafe(close_all_channels(), self.loop).result(30)
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(10)
