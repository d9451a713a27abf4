ALIAS_OF = "sched_wait_ms"
