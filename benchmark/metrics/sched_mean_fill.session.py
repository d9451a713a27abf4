ALIAS_OF = "sched_mean_fill"
