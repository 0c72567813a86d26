class FusedWindowOperator:
    def _dispatch(self, group, wms):
        # SEEDED: the operator picks the pipeline's method by the prologue
        if self.prologue is not None:
            return self.pipe.process_record_group(group, wms)
        return self.pipe.process_superbatch(group, wms)
