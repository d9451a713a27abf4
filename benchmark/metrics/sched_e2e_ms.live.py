ALIAS_OF = "sched_e2e_ms"
