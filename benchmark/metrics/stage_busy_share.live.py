ALIAS_OF = "stage_busy_share"
