# protocheck: stands-for=runtime.py
"""RTL504 bad fixture: the agent spawn path stopped
consuming _worker_config_env, and a counter aggregated from worker
deltas never reaches transfer_stats()."""


class RuntimeLike:
    def _worker_config_env(self):
        return {"RAY_TPU_OBJECT_POOL_SIZE": "4"}

    def _spawn_worker(self):
        env = {}
        env.update(self._worker_config_env())
        return env

    def _spawn_worker_via_agent(self):  # EXPECT: RTL504
        overrides = {}
        return overrides

    def _handle(self, msg):
        tag = msg[0]
        if tag == "xfer_stats":
            d = msg[1]
            self.deduped_pulls += d.get("deduped_pulls", 0)
            self.spillbacks += d.get("spillbacks", 0)  # EXPECT: RTL504

    def transfer_stats(self):
        return {"deduped_pulls": self.deduped_pulls}
