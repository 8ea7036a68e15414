/* The check's H.264 decoder: libavcodec, which shares nothing with the
 * encoder under test. One decoder per stream; each access unit in, the
 * decoded YUV planes out, 4:2:0 or 4:4:4 as the caller expects, in
 * decode order (the streams carry no B frames, so output order is decode
 * order under LOW_DELAY).
 *
 * Built at run time by reference/decoders.py:
 *   gcc -O2 -shared -fPIC -o avdec.so avdec.c -lavcodec -lavutil
 */

#include <libavcodec/avcodec.h>
#include <libavutil/frame.h>
#include <stdlib.h>
#include <string.h>

typedef struct {
    AVCodecContext *ctx;
    AVFrame *frame;
    AVPacket *pkt;
} Dec;

void *bd_open(void)
{
    const AVCodec *codec = avcodec_find_decoder(AV_CODEC_ID_H264);
    if (!codec)
        return NULL;
    Dec *d = calloc(1, sizeof(Dec));
    if (!d)
        return NULL;
    d->ctx = avcodec_alloc_context3(codec);
    d->frame = av_frame_alloc();
    d->pkt = av_packet_alloc();
    if (!d->ctx || !d->frame || !d->pkt) {
        free(d);
        return NULL;
    }
    d->ctx->flags |= AV_CODEC_FLAG_LOW_DELAY;
    d->ctx->thread_count = 1;
    /* a damaged or non-conforming stream is an error */
    d->ctx->err_recognition = AV_EF_EXPLODE | AV_EF_BITSTREAM;
    if (avcodec_open2(d->ctx, codec, NULL) < 0) {
        free(d);
        return NULL;
    }
    return d;
}

/* Feed one access unit; 0 with a picture in the buffers, 1 when the
 * decoder wants more data, < 0 on an error. w and h are the buffers'
 * luma size; c444 says which sampling the stream promises: 0 for 4:2:0
 * (Cb and Cr buffers of w/2 x h/2), 1 for 4:4:4 (w x h). A picture of
 * another size is -1001, of another format or sampling -1002. A damaged
 * stream fails in avcodec itself (AV_EF_EXPLODE). */
int bd_decode(void *h, const uint8_t *data, int size, int w, int hgt,
              int c444, uint8_t *y, uint8_t *u, uint8_t *v)
{
    Dec *d = (Dec *)h;
    int ret = av_new_packet(d->pkt, size);
    if (ret < 0)
        return ret;
    memcpy(d->pkt->data, data, size);
    ret = avcodec_send_packet(d->ctx, d->pkt);
    av_packet_unref(d->pkt);
    if (ret < 0)
        return ret;
    ret = avcodec_receive_frame(d->ctx, d->frame);
    if (ret == AVERROR(EAGAIN))
        return 1;
    if (ret < 0)
        return ret;
    int fmt = d->frame->format;
    int bad = 0;
    if (d->frame->width != w || d->frame->height != hgt)
        bad = -1001;
    else if (c444 ? fmt != AV_PIX_FMT_YUV444P && fmt != AV_PIX_FMT_YUVJ444P
                  : fmt != AV_PIX_FMT_YUV420P && fmt != AV_PIX_FMT_YUVJ420P)
        bad = -1002;
    if (bad) {
        av_frame_unref(d->frame);
        return bad;
    }
    for (int r = 0; r < hgt; r++)
        memcpy(y + (size_t)r * w,
               d->frame->data[0] + (size_t)r * d->frame->linesize[0], w);
    int cw = c444 ? w : w / 2, ch = c444 ? hgt : hgt / 2;
    for (int r = 0; r < ch; r++) {
        memcpy(u + (size_t)r * cw,
               d->frame->data[1] + (size_t)r * d->frame->linesize[1], cw);
        memcpy(v + (size_t)r * cw,
               d->frame->data[2] + (size_t)r * d->frame->linesize[2], cw);
    }
    av_frame_unref(d->frame);
    return 0;
}

void bd_close(void *h)
{
    Dec *d = (Dec *)h;
    if (!d)
        return;
    avcodec_free_context(&d->ctx);
    av_frame_free(&d->frame);
    av_packet_free(&d->pkt);
    free(d);
}
