# protocheck: stands-for=runtime.py
"""RTL504 good fixture: both spawn paths consume
_worker_config_env, and every aggregated counter is surfaced."""


class RuntimeLike:
    def _worker_config_env(self):
        return {
            "RAY_TPU_LEASE_SLOTS": "8",
            "RAY_TPU_OBJECT_POOL_SIZE": "4",
            "RAY_TPU_POOL_BYTES": "1",
        }

    def _spawn_worker(self):
        env = {}
        env.update(self._worker_config_env())
        return env

    def _spawn_worker_via_agent(self):
        overrides = {}
        overrides.update(self._worker_config_env())
        return overrides

    def _handle(self, msg):
        tag = msg[0]
        if tag == "xfer_stats":
            d = msg[1]
            self.deduped_pulls += d.get("deduped_pulls", 0)
            self.spillbacks += d.get("spillbacks", 0)

    def transfer_stats(self):
        return {"deduped_pulls": self.deduped_pulls,
                "spillbacks": self.spillbacks}
